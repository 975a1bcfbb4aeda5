"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth
used by the per-kernel allclose sweeps in tests/)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def fedavg_agg_ref(stacked, weights):
    """stacked: (C, N) client-stacked flat params; weights: (C,) sum=1.
    Full f32 precision (a TPU's default f32 matmul multiplies in bf16)."""
    return jnp.einsum("c,cn->n", weights.astype(jnp.float32),
                      stacked.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST
                      ).astype(stacked.dtype)


def trimmed_mean_ref(stacked, trim: int):
    """Sort-based oracle for the bitonic-select `robust_agg` kernel: mean
    over the order statistics of rank trim..C-trim-1 per coordinate. Tie
    values are interchangeable, so any correct selection sums
    identically. Oracle ONLY — XLA:CPU lowers `jnp.sort` to a
    comparator-driven sort that is ~8x slower than the kernel's
    vectorized min/max network (`robust_agg.trimmed_mean_jnp` is the
    production CPU path)."""
    C = stacked.shape[0]
    if not 0 <= 2 * trim < C:
        raise ValueError(f"trim={trim} invalid for C={C} clients")
    s = jnp.sort(stacked.astype(jnp.float32), axis=0)
    return jnp.mean(s[trim:C - trim], axis=0).astype(stacked.dtype)


def median_ref(stacked):
    return trimmed_mean_ref(stacked, (stacked.shape[0] - 1) // 2)


def flash_attention_ref(q, k, v, *, causal=True, window=0):
    """q: (BH, S, d), k/v: (BH, T, d) — plain softmax attention."""
    BH, S, d = q.shape
    T = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    ok = jnp.ones((S, T), bool)
    if causal:
        ok &= kpos <= qpos
    if window and window > 0:
        ok &= kpos > qpos - window
    logits = jnp.where(ok[None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bst,btd->bsd", w, v.astype(jnp.float32)
                      ).astype(q.dtype)


def ssm_scan_ref(xh, a_log, dt, Bm, Cm, h0=None):
    """Exact sequential SSD recurrence (the oracle for the chunked kernel).

    xh: (B,S,H,dh)  a_log: (B,S,H)  dt: (B,S,H)  Bm/Cm: (B,S,N).
    Returns (y: (B,S,H,dh), hT: (B,H,dh,N))."""
    B, S, H, dh = xh.shape
    N = Bm.shape[-1]
    f32 = jnp.float32

    def step(h, t):
        a_t, dt_t, B_t, C_t, x_t = t
        h = (jnp.exp(a_t)[:, :, None, None] * h
             + jnp.einsum("bh,bn,bhd->bhdn", dt_t, B_t, x_t))
        y = jnp.einsum("bn,bhdn->bhd", C_t, h)
        return h, y

    init = jnp.zeros((B, H, dh, N), f32) if h0 is None else h0.astype(f32)
    ts = (jnp.moveaxis(a_log.astype(f32), 1, 0),
          jnp.moveaxis(dt.astype(f32), 1, 0),
          jnp.moveaxis(Bm.astype(f32), 1, 0),
          jnp.moveaxis(Cm.astype(f32), 1, 0),
          jnp.moveaxis(xh.astype(f32), 1, 0))
    hT, ys = jax.lax.scan(step, init, ts)
    return jnp.moveaxis(ys, 0, 1).astype(xh.dtype), hT
