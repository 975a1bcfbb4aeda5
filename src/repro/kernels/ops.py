"""Jit'd public wrappers for the Pallas kernels.

On TPU the pallas_call path runs natively; on CPU (this container) the
wrappers run the kernels in interpret mode (tests) or fall back to the
pure-jnp reference (production CPU paths), so every caller is portable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import comm_agg as _ca
from repro.kernels import fedavg_agg as _fa
from repro.kernels import flash_attention as _fl
from repro.kernels import gossip_mix as _gm
from repro.kernels import robust_agg as _ra
from repro.kernels import ssm_scan as _ss
from repro.kernels import ref


@functools.cache
def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# -- fedavg ------------------------------------------------------------------

def fedavg_aggregate(stacked, weights, *, interpret=None):
    interpret = on_cpu() if interpret is None else interpret
    return _fa.fedavg_agg(stacked, weights, interpret=interpret)


# -- fused dequantize + aggregate (upload codecs, DESIGN.md §12) --------------
# The device fast path for the plain-FedAvg reduce over int8-quantized
# uploads. Like the robust kernel, the CPU default is the pure-jnp
# reference (`dequant_agg_jnp` — a single fused XLA reduce, also the
# path the generic round driver traces) and tests opt into the Pallas
# kernel with interpret=True.

def dequant_aggregate(values, scales, weights, *, interpret=None):
    if interpret is None and on_cpu():
        return _ca.dequant_agg_jnp(values, scales, weights)
    return _ca.dequant_agg(values, scales, weights,
                           interpret=bool(interpret))


# -- masked gossip mixing (fault injection / moving-target topologies,
# DESIGN.md §15) --------------------------------------------------------------
# The per-round (C, C) mixing matmul for gossip under dynamic membership:
# the mix matrix is a fresh array every round (masked rows, heartbeat
# decay, MTD ring re-randomization), so the static-graph constant-fold of
# `gossip_stacked` doesn't apply. CPU default is the pure-jnp matmul
# (also what the fused executor traces in-scan); tests opt into the
# Pallas kernel with interpret=True.

def masked_gossip_aggregate(stacked, mix, *, interpret=None):
    if interpret is None and on_cpu():
        return _gm.gossip_mix_jnp(stacked, mix)
    return _gm.gossip_mix_agg(stacked, mix, interpret=bool(interpret))


# -- robust aggregation (trimmed mean / median) -------------------------------
# The selection kernel is a tiled bitonic sorting network over the client
# axis; its interpret-mode emulation re-runs the grid loop in jnp and is
# slower than just applying the same network to the whole matrix, so on
# CPU the default is the jnp network (`trimmed_mean_jnp` — the
# production fallback, which also traces cleanly into the fused
# executor's round scan) and tests opt into the kernel with
# interpret=True. The sort-based `ref.trimmed_mean_ref` stays the
# correctness oracle only: XLA:CPU's comparator sort is ~8x slower than
# the vectorized network at C=64.

def trimmed_mean_aggregate(stacked, trim, *, interpret=None):
    if interpret is None and on_cpu():
        return _ra.trimmed_mean_jnp(stacked, trim)
    return _ra.trimmed_mean_agg(stacked, trim,
                                interpret=bool(interpret))


def median_aggregate(stacked, *, interpret=None):
    return trimmed_mean_aggregate(stacked, (stacked.shape[0] - 1) // 2,
                                  interpret=interpret)


# The flatten/ravel path: every aggregation event in the vectorized engine
# (FedAvg, HFL tiers, masked AFL, CFL merge) funnels its stacked pytree
# through these three helpers onto the fused kernel's (C, N) layout.

def stacked_ravel(stacked_tree):
    """Pytree with leading client axis -> (C, N) float32 matrix (leaves
    flattened and concatenated in tree-flatten order)."""
    leaves = jax.tree.leaves(stacked_tree)
    C = leaves[0].shape[0]
    return jnp.concatenate(
        [l.reshape(C, -1).astype(jnp.float32) for l in leaves], axis=1)


def stacked_unravel(template_stacked, mat):
    """(M, N) matrix -> pytree with leading axis M, trailing shapes/dtypes
    taken from `template_stacked` (its own leading axis is ignored, so the
    template may have a different client count than M)."""
    leaves, treedef = jax.tree_util.tree_flatten(template_stacked)
    M = mat.shape[0]
    out, off = [], 0
    for l in leaves:
        sz = int(np.prod(l.shape[1:], dtype=np.int64))
        out.append(mat[:, off:off + sz].reshape((M,) + l.shape[1:])
                   .astype(l.dtype))
        off += sz
    return jax.tree_util.tree_unflatten(treedef, out)


def tree_unravel(template, vec):
    """(N,) aggregated vector -> single pytree shaped like `template` with
    its leading client axis dropped (pass a stacked tree as template)."""
    leaves, treedef = jax.tree_util.tree_flatten(template)
    out, off = [], 0
    for l in leaves:
        sz = int(np.prod(l.shape[1:], dtype=np.int64))
        out.append(vec[off:off + sz].reshape(l.shape[1:]).astype(l.dtype))
        off += sz
    return jax.tree_util.tree_unflatten(treedef, out)


def fedavg_aggregate_stacked(stacked_tree, weights, *, interpret=None):
    """Kernel-backed FedAvg of a stacked pytree: ravel -> fused weighted
    reduction -> unravel. `weights` must already be normalized."""
    mat = stacked_ravel(stacked_tree)
    return tree_unravel(stacked_tree,
                        fedavg_aggregate(mat, weights, interpret=interpret))


def fedavg_aggregate_tree(client_params, weights, *, interpret=None):
    """FedAvg a *list* of pytrees through the fused kernel (host-level
    callers); stacks then reuses the ravel path."""
    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *client_params)
    return fedavg_aggregate_stacked(stacked, weights, interpret=interpret)


def merge_aggregate_stacked(base_tree, stacked_tree, weights, *,
                            interpret=None):
    """Weighted variant of the `fedavg_aggregate_stacked` ravel path with
    a distinguished base row: the async engine's batched merge.

    `base_tree` is the server model (no client axis), `stacked_tree` holds
    k arriving client updates (leading axis k), `weights` is a (k+1,)
    already-normalized vector whose first entry weights the base model.
    One fused kernel pass over the (k+1, N) matrix replaces k sequential
    `cfl_merge` host calls (see strategies.async_batch_merge for the
    weight composition that makes the two exactly equivalent)."""
    base_row = stacked_ravel(jax.tree.map(lambda l: l[None], base_tree))
    mat = jnp.concatenate([base_row, stacked_ravel(stacked_tree)], axis=0)
    return tree_unravel(stacked_tree,
                        fedavg_aggregate(mat, weights, interpret=interpret))


# -- flash attention -----------------------------------------------------------

def flash_attention(q, k, v, *, causal=True, window=0, interpret=None,
                    block_q=128, block_k=128):
    """q: (B,S,H,d); k/v: (B,T,Hk,d) — GQA folded by repeating KV heads.

    Returns (B,S,H,d)."""
    interpret = on_cpu() if interpret is None else interpret
    B, S, H, d = q.shape
    Hk = k.shape[2]
    if H != Hk:
        rep = H // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, S, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * H, -1, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * H, -1, d)
    of = _fl.flash_attention(qf, kf, vf, causal=causal, window=window,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return jnp.moveaxis(of.reshape(B, H, S, d), 1, 2)


# -- ssm scan ------------------------------------------------------------------

def ssm_scan(xh, a_log, dt, Bm, Cm, *, chunk=128, interpret=None):
    interpret = on_cpu() if interpret is None else interpret
    return _ss.ssm_scan(xh, a_log, dt, Bm, Cm, chunk=chunk,
                        interpret=interpret)
