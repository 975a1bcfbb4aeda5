"""Aggregation operators — the paper's contribution as composable ops.

(Formerly `core/strategies.py`; that module now hosts the Strategy
plugin API and re-exports these names with a DeprecationWarning.)

Two implementations of the same math, validated against each other in
tests:

* HOST level — operates on a *list* of client parameter pytrees (the
  paper-faithful simulation on CPU; arbitrary client counts).
* MESH level — operates inside `shard_map` where the leading "clients"
  axis of every parameter is sharded over a mesh axis; aggregation
  lowers to `jax.lax` collectives (psum / collective_permute), which is
  what the multi-pod dry-run compiles and the roofline's collective
  term measures:

      HFL  -> two psums (axis_index_groups tier, then global tier)
              [multi-pod: psum over "data" then psum over "pod"]
      AFL  -> masked weighted psum (fedavg mode)
              ring collective_permute exchange (gossip mode)
      CFL  -> psum + EMA continual merge (see DESIGN.md §2 adaptation)

All operators implement Eq. (5): theta_g = sum_c (n_c / N) theta_c,
generalized with per-client weights / participation masks.
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topology

Params = Any


# ===========================================================================
# host-level (list-of-pytrees) operators — used by the paper simulation
# ===========================================================================

def fedavg(client_params: List[Params],
           weights: Optional[Sequence[float]] = None,
           use_kernel: bool = False) -> Params:
    """Weighted parameter average over clients (Eq. 5)."""
    n = len(client_params)
    w = np.ones(n) if weights is None else np.asarray(weights, np.float64)
    w = (w / w.sum()).astype(np.float32)
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.fedavg_aggregate_tree(client_params, jnp.asarray(w))
    return jax.tree.map(
        lambda *leaves: sum(wi * l for wi, l in zip(w, leaves)),
        *client_params)


def defended_fedavg(client_params: List[Params],
                    weights: Optional[Sequence[float]] = None, *,
                    defense: str = "none", f: int = 1, tau: float = 10.0,
                    center: Optional[Params] = None) -> Params:
    """Host-level robust FedAvg (loop engine's aggregation events): stack
    the client list and dispatch through `core.robust` — exactly the
    stacked engine's defended operator, so the engines share one defense
    implementation (DESIGN.md §8)."""
    if defense in ("none", None):
        return fedavg(client_params, weights)
    from repro.core import robust
    from repro.core.engine import stack_forest
    return robust.robust_aggregate_stacked(
        stack_forest(list(client_params)), defense, weights=weights,
        f=f, tau=tau, center=center)


def hfl_aggregate(client_params: List[Params], groups: List[List[int]],
                  weights: Optional[Sequence[float]] = None, *,
                  defense: str = "none", f: int = 1, tau: float = 10.0,
                  centers: Optional[List[Params]] = None) -> Params:
    """Two-tier FedAvg: per-group aggregate, then global over group models,
    weighted by group sample counts. A defense applies at tier 1 — the
    group server is the first aggregation boundary Byzantine clients hit;
    tier 2 averages group SERVER models, which the threat model trusts
    (DESIGN.md §8). `centers` (per-group round-start models) feed
    norm_clip; `f` is the per-group Byzantine allowance."""
    w = (np.ones(len(client_params)) if weights is None
         else np.asarray(weights, np.float64))
    group_models, group_w = [], []
    for gi, g in enumerate(groups):
        group_models.append(defended_fedavg(
            [client_params[c] for c in g], weights=[w[c] for c in g],
            defense=defense, f=f, tau=tau,
            center=None if centers is None else centers[gi]))
        group_w.append(sum(w[c] for c in g))
    return fedavg(group_models, weights=group_w)


def afl_aggregate(client_params: List[Params], participants: Sequence[int],
                  weights: Optional[Sequence[float]] = None) -> Params:
    """FedAvg over the sampled participant subset (paper's AFL round)."""
    w = (np.ones(len(client_params)) if weights is None
         else np.asarray(weights, np.float64))
    return fedavg([client_params[c] for c in participants],
                  weights=[w[c] for c in participants])


def gossip_round(client_params: List[Params],
                 neighbors: List[List[int]], *,
                 defense: str = "none", f: int = 1) -> List[Params]:
    """One synchronous gossip exchange: every client averages with its
    ring neighbors — or, defended, takes the coordinate-wise median /
    trimmed mean of its neighborhood (each honest node bounds what a
    Byzantine neighbor can inject; norm_clip/krum don't apply to the
    tiny neighborhood sets). Returns the new per-client model list."""
    out = []
    for c, nbrs in enumerate(neighbors):
        members = [client_params[c]] + [client_params[j] for j in nbrs]
        out.append(defended_fedavg(members, defense=defense, f=f))
    return out


def cfl_merge(global_params: Params, client_params: Params,
              alpha: float) -> Params:
    """Continual merge: theta_g <- (1-alpha) theta_g + alpha theta_c."""
    return jax.tree.map(
        lambda g, c: ((1.0 - alpha) * g.astype(jnp.float32)
                      + alpha * c.astype(jnp.float32)).astype(g.dtype),
        global_params, client_params)


# ===========================================================================
# stacked-array operators — the vectorized engine's aggregation events
# ===========================================================================
# These operate on ONE pytree whose leaves carry a leading client axis
# (core/engine.py). Every weighted reduction lowers onto the Pallas
# `fedavg_agg` kernel through the ravel path in kernels/ops.py (interpret
# mode on CPU, native on TPU); gossip is a dense mixing matmul (each
# output row mixes several inputs — not a single weighted reduction).


def tree_where(flag, on_true: Params, on_false: Params) -> Params:
    """Per-leaf `jnp.where` over two identically-shaped pytrees with a
    scalar (possibly traced) boolean — how schedule conditionals that
    are Python `if`s in the per-round driver (e.g. HFL's every-Nth-round
    global dissemination) are expressed inside the fused executor's
    round scan (DESIGN.md §10)."""
    return jax.tree.map(lambda a, b: jnp.where(flag, a, b),
                        on_true, on_false)


def _stacked_weights(n: int, weights) -> jnp.ndarray:
    w = (jnp.ones((n,), jnp.float32) if weights is None
         else jnp.asarray(weights, jnp.float32))
    return _safe_normalize(w, n)


def _safe_normalize(w: jnp.ndarray, n: int) -> jnp.ndarray:
    """w / sum(w), guarded against a zero total (an all-masked
    participant column under fault injection — DESIGN.md §15): the
    degenerate case degrades to the uniform average instead of NaN-ing
    the weight sum. Bitwise-preserving: when sum(w) > 0 the selects
    resolve to exactly the unguarded `w / jnp.sum(w)`."""
    s = jnp.sum(w)
    safe = jnp.where(s > 0, w, jnp.ones_like(w))
    return safe / jnp.where(s > 0, s, jnp.asarray(float(n), jnp.float32))


def _row_mask(alive, leaf) -> jnp.ndarray:
    """(C,) alive mask broadcast as a boolean against a (C, ...) leaf."""
    m = jnp.asarray(alive, jnp.float32) > 0
    return m.reshape(m.shape + (1,) * (leaf.ndim - 1))


def mask_rows(stacked: Params, alive, fallback: Params) -> Params:
    """Rows of the stacked pytree where `alive` is 0 are replaced by the
    broadcast `fallback` pytree (no leading client axis) — the
    upload-loss seam: a dead participant's slot carries "no update"
    (the event's center model) into order-statistic defenses
    (DESIGN.md §15)."""
    return jax.tree.map(
        lambda p, f: jnp.where(_row_mask(alive, p), p,
                               f[None].astype(p.dtype)),
        stacked, fallback)


def tree_where_rows(mask, on_true: Params, on_false: Params) -> Params:
    """Per-row `jnp.where` between two identically-stacked pytrees with
    a (C,) boolean row mask (per-group quorum holds in HFL tier 1)."""
    return jax.tree.map(
        lambda a, b: jnp.where(_row_mask(mask, a), a, b),
        on_true, on_false)


def fedavg_stacked(stacked: Params, weights=None, *,
                   interpret=None) -> Params:
    """Kernel-backed Eq. (5) over a stacked federation -> single pytree."""
    from repro.kernels import ops as kops
    n = jax.tree.leaves(stacked)[0].shape[0]
    return kops.fedavg_aggregate_stacked(
        stacked, _stacked_weights(n, weights), interpret=interpret)


def defended_aggregate_stacked(stacked: Params, weights=None, *,
                               defense: str = "none", f: int = 1,
                               tau: float = 10.0, center=None,
                               interpret=None, alive=None) -> Params:
    """One defended aggregation event on the stack: plain kernel FedAvg
    when `defense` is "none", else the `core.robust` operator family
    (median / trimmed-mean selection kernel, norm_clip with `center`,
    Krum). The single dispatch point every strategy's robust variant
    funnels through.

    `alive` (fault injection, DESIGN.md §15) is a (C,) 0/1 mask: dead
    participants' weights are zeroed (survivors renormalize through the
    guarded normalizer — an all-dead event degrades to `center`) and,
    when a `center` is given, their rows are substituted by it so
    order-statistic defenses see "no update" rather than a lost upload's
    stale parameters. alive=None is the exact pre-fault path."""
    if alive is not None:
        n = jax.tree.leaves(stacked)[0].shape[0]
        w = (jnp.ones((n,), jnp.float32) if weights is None
             else jnp.asarray(weights, jnp.float32))
        weights = w * jnp.asarray(alive, jnp.float32)
        if center is not None:
            stacked = mask_rows(stacked, alive, center)
    if defense in ("none", None):
        return fedavg_stacked(stacked, weights, interpret=interpret)
    from repro.core import robust
    return robust.robust_aggregate_stacked(
        stacked, defense, weights=weights, f=f, tau=tau, center=center,
        interpret=interpret)


def hfl_tier1_stacked(stacked: Params, num_groups: int, weights=None, *,
                      defense: str = "none", f: int = 1, tau: float = 10.0,
                      centers: Params = None, interpret=None, alive=None):
    """Group-server aggregation over the contiguous equal-size groups of
    `topology.hierarchical_groups`: (C, ...) -> ((G, ...) group models,
    (G,) group sample-weight totals) — one kernel call per group.

    A defense applies here, at the first aggregation boundary Byzantine
    clients reach (DESIGN.md §8): each group server robust-aggregates its
    own slice. `centers` is the (G, ...) stacked round-start group models
    (norm_clip's reference); `f` is the per-group Byzantine allowance.

    `alive` (fault injection, DESIGN.md §15) masks dead clients out of
    their group's weights (guarded renormalize; a fully-dead group
    degrades to its center — the group server holds its round-start
    model) and substitutes their raveled rows by the group center so
    order-statistic defenses see "no update". Group TOTALS stay the
    full sample weights either way: a degraded group server still
    reports a model at tier 2 with its full population weight."""
    from repro.core import robust
    from repro.kernels import ops as kops
    mat = kops.stacked_ravel(stacked)
    C = mat.shape[0]
    if C % num_groups:
        raise ValueError(f"{C} clients not divisible into {num_groups} groups")
    per = C // num_groups
    w = (jnp.ones((C,), jnp.float32) if weights is None
         else jnp.asarray(weights, jnp.float32))
    center_rows = (kops.stacked_ravel(centers) if centers is not None
                   else None)
    rows, totals = [], []
    for g in range(num_groups):
        wg = w[g * per:(g + 1) * per]
        gmat = mat[g * per:(g + 1) * per]
        if alive is not None:
            alive_g = jnp.asarray(alive, jnp.float32)[g * per:(g + 1) * per]
            wg_eff = wg * alive_g
            if center_rows is not None:
                gmat = jnp.where(alive_g[:, None] > 0, gmat,
                                 center_rows[g][None])
        else:
            wg_eff = wg
        if defense in ("none", None):
            rows.append(kops.fedavg_aggregate(
                gmat, _safe_normalize(wg_eff, per), interpret=interpret))
        else:
            rows.append(robust.robust_aggregate(
                gmat, defense, weights=wg_eff, f=f, tau=tau,
                center=None if center_rows is None else center_rows[g],
                interpret=interpret))
        totals.append(jnp.sum(wg))
    return (kops.stacked_unravel(stacked, jnp.stack(rows)),
            jnp.stack(totals))


def hfl_aggregate_stacked(stacked: Params, num_groups: int, weights=None, *,
                          defense: str = "none", f: int = 1,
                          tau: float = 10.0, centers: Params = None,
                          interpret=None) -> Params:
    """Two-tier HFL on the stack: tier-1 group kernels (optionally
    defended), tier-2 kernel over the (G, ...) group models weighted by
    group totals (group servers are trusted — DESIGN.md §8)."""
    groups, gw = hfl_tier1_stacked(stacked, num_groups, weights,
                                   defense=defense, f=f, tau=tau,
                                   centers=centers, interpret=interpret)
    return fedavg_stacked(groups, gw, interpret=interpret)


def afl_aggregate_stacked(stacked: Params, weights=None, participate=None, *,
                          interpret=None, alive=None) -> Params:
    """Masked FedAvg over sampled participants: `participate` is a (C,)
    0/1 mask folded into the kernel weights (non-participants contribute
    zero; at least one participant required). `alive` (fault injection)
    folds in the same way — dead participants' uploads are lost on the
    wire and carry zero weight; the guarded normalizer handles the
    all-dead edge (DESIGN.md §15)."""
    n = jax.tree.leaves(stacked)[0].shape[0]
    w = (jnp.ones((n,), jnp.float32) if weights is None
         else jnp.asarray(weights, jnp.float32))
    if participate is not None:
        w = w * jnp.asarray(participate, jnp.float32)
    if alive is not None:
        w = w * jnp.asarray(alive, jnp.float32)
    return fedavg_stacked(stacked, w, interpret=interpret)


def gossip_mix_matrix(neighbors: List[List[int]]) -> np.ndarray:
    """The (C, C) row-stochastic gossip mixing matrix: row c averages
    client c with its neighbors, uniformly. Shared by the single-device
    mixing matmul (`gossip_stacked`) and the mesh-sharded all-to-all
    (`mesh_gossip_stacked`), so the two paths can never mix different
    graphs."""
    C = len(neighbors)
    mix = np.zeros((C, C), np.float32)
    for c, nbrs in enumerate(neighbors):
        members = [c] + list(nbrs)
        mix[c, members] = 1.0 / len(members)
    return mix


def gossip_stacked(stacked: Params, neighbors: List[List[int]], *,
                   defense: str = "none", f: int = 1) -> Params:
    """Synchronous ring gossip on the stack. Undefended: a (C, C)
    row-stochastic mixing matrix (self + neighbors, uniform) applied to
    the raveled parameter matrix — matches host `gossip_round` exactly.

    Defended (median / trimmed_mean): each client takes the trimmed mean
    of its gathered neighborhood instead. That is no longer a linear
    mixing (selection per coordinate per neighborhood), so it runs as one
    batched sort over the (C, K, N) gathered tensor rather than the
    selection kernel — neighborhoods are tiny (K = degree + 1), the
    client axis provides the parallelism. Matches the defended host
    `gossip_round` exactly (equal-size ring neighborhoods)."""
    from repro.kernels import ops as kops
    mat = kops.stacked_ravel(stacked)
    C = mat.shape[0]
    if defense in ("none", None):
        mix = gossip_mix_matrix(neighbors)
        return kops.stacked_unravel(stacked, jnp.asarray(mix) @ mat)
    if defense not in ("median", "trimmed_mean"):
        raise ValueError(f"gossip mixing supports median/trimmed_mean "
                         f"defenses, not {defense!r} (DESIGN.md §8)")
    sizes = {len(n) for n in neighbors}
    if len(sizes) != 1:
        raise ValueError("defended gossip needs equal-size neighborhoods "
                         "(ring topology)")
    K = sizes.pop() + 1
    idx = np.stack([np.asarray([c] + list(nbrs))
                    for c, nbrs in enumerate(neighbors)])       # (C, K)
    gathered = jnp.sort(mat[jnp.asarray(idx)], axis=1)          # (C, K, N)
    t = (K - 1) // 2 if defense == "median" else min(f, (K - 1) // 2)
    mixed = jnp.mean(gathered[:, t:K - t], axis=1)
    return kops.stacked_unravel(stacked, mixed)


def masked_gossip_stacked(stacked: Params, *, mix=None, gather_idx=None,
                          defense: str = "none", f: int = 1,
                          interpret=None) -> Params:
    """Gossip under dynamic membership (fault injection, DESIGN.md §15):
    the per-round twin of `gossip_stacked` whose graph is an ARRAY, not
    a static neighbor list — the fault schedule precomputes, per round,
    either the masked row-stochastic mixing matrix `mix` (undefended:
    dead rows identity, heartbeat-decayed supports, optionally the
    re-randomized moving-target ring) or the `gather_idx` neighborhood
    tensor (defended: dead/decayed neighbors substituted by self so the
    sorted neighborhood keeps its static K). Both the per-round drivers
    and the fused executor consume the same arrays (there as scan
    inputs), so the mixing math is engine-bitwise by construction."""
    from repro.kernels import ops as kops
    mat = kops.stacked_ravel(stacked)
    if defense in ("none", None):
        mixed = kops.masked_gossip_aggregate(
            mat, jnp.asarray(mix, jnp.float32), interpret=interpret)
        return kops.stacked_unravel(stacked, mixed)
    if defense not in ("median", "trimmed_mean"):
        raise ValueError(f"gossip mixing supports median/trimmed_mean "
                         f"defenses, not {defense!r} (DESIGN.md §8)")
    idx = jnp.asarray(gather_idx, jnp.int32)
    K = idx.shape[1]
    gathered = jnp.sort(mat[idx], axis=1)                       # (C, K, N)
    t = (K - 1) // 2 if defense == "median" else min(f, (K - 1) // 2)
    mixed = jnp.mean(gathered[:, t:K - t], axis=1)
    return kops.stacked_unravel(stacked, mixed)


def cfl_merge_stacked(global_params: Params, client_params: Params,
                      alpha, *, interpret=None) -> Params:
    """Continual merge as a C=2 kernel reduction with weights
    (1-alpha, alpha) — same math as host `cfl_merge`, kernel-routed.
    Traceable (alpha may be a tracer), so it composes with lax.scan."""
    stacked = jax.tree.map(lambda g, c: jnp.stack([g, c]),
                           global_params, client_params)
    alpha = jnp.asarray(alpha, jnp.float32)
    return fedavg_stacked(stacked, jnp.stack([1.0 - alpha, alpha]),
                          interpret=interpret)


def defended_cfl_merge(global_params: Params, client_params: Params,
                       alpha, tau: float, *, interpret=None) -> Params:
    """norm_clip-defended continual merge: the arriving update's delta is
    L2-clipped against the current global model before the EMA fold — the
    only defense available at a redundancy-1 merge event (DESIGN.md §8).
    Traceable (used inside the vectorized CFL scan); the loop engine
    applies the identical clip before its host `cfl_merge`."""
    from repro.core import robust
    clipped = robust.clip_deltas_stacked(
        global_params, jax.tree.map(lambda l: l[None], client_params), tau)
    return cfl_merge_stacked(global_params,
                             jax.tree.map(lambda l: l[0], clipped),
                             alpha, interpret=interpret)


def staleness_batch_weights(alphas) -> jnp.ndarray:
    """Weights that make ONE weighted reduction equal k SEQUENTIAL
    continual merges with rates alphas[0..k-1] (in that order):

        theta <- (1-a_i) theta + a_i theta_i   for i = 0..k-1

    composes to  theta * prod_j (1-a_j)
                 + sum_i theta_i * a_i * prod_{j>i} (1-a_j),

    so the returned (k+1,) vector is [prod(1-a), a_0*suffix_0, ...,
    a_{k-1}*1] with suffix_i = prod_{j>i}(1-a_j). The entries telescope
    to sum exactly 1 — no renormalization needed (DESIGN.md §5)."""
    a = jnp.asarray(alphas, jnp.float32)
    keep = jnp.cumprod((1.0 - a)[::-1])[::-1]         # prod_{j>=i}(1-a_j)
    suffix = jnp.concatenate([keep[1:], jnp.ones((1,), jnp.float32)])
    return jnp.concatenate([keep[:1], a * suffix])


def async_batch_merge(global_params: Params, stacked_updates: Params,
                      alphas, *, interpret=None) -> Params:
    """Batched staleness-aware merge: fold k same-tick client arrivals
    (leading axis k, per-arrival rates `alphas`) into the server model in
    one kernel pass — exactly equivalent to k sequential `cfl_merge`
    calls (tests/test_async_engine.py pins the equivalence).

    k = 0 (a tick in which every scheduled arrival dropped) is a defined
    no-op returning the server model unchanged — the empty weight vector
    would otherwise feed a zero-denominator staleness merge through the
    kernel (regression-pinned in tests/test_async_engine.py)."""
    k = (alphas.shape[0] if hasattr(alphas, "shape") else len(alphas))
    if k == 0:
        return global_params
    from repro.kernels import ops as kops
    return kops.merge_aggregate_stacked(
        global_params, stacked_updates, staleness_batch_weights(alphas),
        interpret=interpret)


# ===========================================================================
# mesh-sharded STACKED operators — the fused executor under shard_map
# (DESIGN.md §11)
# ===========================================================================
# These mirror the stacked-array section above, but run INSIDE shard_map
# with the leading client axis partitioned over a mesh axis: every
# device holds a contiguous (C_loc, ...) sub-stack of clients, local
# math stays per-shard, and each aggregation event lowers to exactly its
# collective (weighted psum / grouped psum / masked all-to-all mix).
# Plain jnp + jax.lax collectives only — the Pallas ravel path stays on
# the single-device side (interpret-mode kernels inside shard_map would
# trace the kernel body per shard for no benefit).


def _bcast(w, p):
    """(C,) weights broadcast against a (C, ...) leaf."""
    return w.reshape(w.shape + (1,) * (p.ndim - 1))


def mesh_fedavg_stacked(stacked: Params, weights, *, axis: str = "data"
                        ) -> Params:
    """Eq. (5) over the SHARDED client axis: each shard reduces its
    local sub-stack, one weighted psum produces the replicated global
    aggregate — the mesh twin of `fedavg_stacked` (AFL star / FedProx /
    server-optimizer events). The denominator is guarded against an
    all-masked federation (fault injection can zero every weight in a
    round; the quorum hold discards the degenerate value, but it must
    not be NaN — DESIGN.md §15); the guard is bitwise-inert whenever
    any weight survives."""
    w = jnp.asarray(weights, jnp.float32)
    den = jax.lax.psum(jnp.sum(w), axis)
    den = jnp.where(den > 0, den, jnp.float32(1.0))
    return jax.tree.map(
        lambda p: (jax.lax.psum(
            jnp.sum(p.astype(jnp.float32) * _bcast(w, p), axis=0), axis)
            / den).astype(p.dtype),
        stacked)


def hfl_tier1_local(stacked: Params, weights, num_groups_local: int, *,
                    alive=None):
    """HFL tier-1 over groups that nest INSIDE one shard: (C_loc, ...)
    -> ((G_loc, ...) group models, (G_loc,) group weight totals), pure
    per-shard math — NO collective. This is the fused mesh executor's
    tier-1 event (groups are required to align to shards, so the group
    boundary never crosses a shard boundary; DESIGN.md §11).

    `alive` (fault injection, DESIGN.md §15) is the shard-local (C_loc,)
    0/1 mask: dead clients are zero-weighted in their group's reduction
    (guarded denominator — a fully-dead group's degenerate value is
    discarded by the caller's per-group quorum hold, but it must not be
    NaN). Group TOTALS stay the full sample weights, matching the
    single-device `hfl_tier1_stacked` semantics."""
    w = jnp.asarray(weights, jnp.float32)
    C_loc = w.shape[0]
    if C_loc % num_groups_local:
        raise ValueError(
            f"{C_loc} local clients not divisible into "
            f"{num_groups_local} local groups")
    per = C_loc // num_groups_local
    wg = w.reshape(num_groups_local, per)
    gw = jnp.sum(wg, axis=1)
    if alive is not None:
        wg = wg * jnp.asarray(alive, jnp.float32).reshape(
            num_groups_local, per)
    gw_eff = jnp.sum(wg, axis=1)
    den = jnp.where(gw_eff > 0, gw_eff, jnp.float32(1.0))

    def tier1(p):
        q = p.astype(jnp.float32).reshape(
            (num_groups_local, per) + p.shape[1:])
        num = jnp.sum(q * wg.reshape((num_groups_local, per)
                                     + (1,) * (p.ndim - 1)), axis=1)
        return (num / _bcast(den, num)).astype(p.dtype)

    return jax.tree.map(tier1, stacked), gw


def mesh_hfl_stacked(stacked: Params, weights, num_groups: int, *,
                     axis: str = "data") -> Params:
    """Two-tier HFL over a SHARDED client stack: the general operator
    behind the `mesh_hfl` parity suite, supporting group sizes above,
    equal to, and below the shard size (the fused executor's own path
    restricts to shard-aligned groups and calls `hfl_tier1_local`
    directly, keeping tier-1 collective-free).

    * group size <= shard size (groups nest in shards): tier 1 is the
      local reshape (`hfl_tier1_local`), tier 2 one weighted psum.
    * group size > shard size (groups span whole shards): tier 1 is a
      grouped psum over `axis_index_groups`. Tier 2 then exploits the
      tier-1 replication within each group: the gw-weighted full-axis
      psum overcounts numerator AND denominator by exactly the group's
      shard count, which cancels (same argument as `mesh_hfl`).

    Matches host `hfl_aggregate` on the gathered stack
    (tests/test_fl_mesh_dryrun.py)."""
    ndev = jax.lax.axis_size(axis)
    w = jnp.asarray(weights, jnp.float32)
    C_loc = w.shape[0]
    C = C_loc * ndev
    if C % num_groups:
        raise ValueError(f"{C} clients not divisible into {num_groups} "
                         f"groups")
    per = C // num_groups
    if per <= C_loc:
        groups, gw = hfl_tier1_local(stacked, w, C_loc // per)
        return mesh_fedavg_stacked(groups, gw, axis=axis)
    if per % C_loc:
        raise ValueError(
            f"group size {per} neither nests in nor spans whole shards "
            f"of {C_loc} clients")
    dev_groups = topology.mesh_axis_groups(ndev, num_groups)
    part = jax.tree.map(
        lambda p: jnp.sum(p.astype(jnp.float32) * _bcast(w, p), axis=0),
        stacked)
    gw = jax.lax.psum(jnp.sum(w), axis, axis_index_groups=dev_groups)
    group = jax.tree.map(
        lambda p: jax.lax.psum(p, axis, axis_index_groups=dev_groups) / gw,
        part)
    # tier 2: each group model is replicated across its per // C_loc
    # member shards, so numerator and denominator both overcount by that
    # count — it cancels
    return jax.tree.map(
        lambda p: ((jax.lax.psum(p * gw, axis)
                    / jax.lax.psum(gw, axis)).astype(jnp.float32)),
        group)


def mesh_gossip_stacked(stacked: Params, mix, *, axis: str = "data"
                        ) -> Params:
    """Synchronous gossip on a SHARDED client stack as a masked
    all-to-all: `mix` is the (C, C) row-stochastic mixing matrix of
    `gossip_stacked` (self + ring neighbors, uniform). Each shard
    multiplies the mixing COLUMNS it owns against its local sub-stack,
    one psum assembles every mixed row, and the shard keeps its own
    row block — the ring exchange expressed as a single collective
    (neighbor models cross shard boundaries; a ppermute chain would pay
    one hop per ring degree instead)."""
    mix = jnp.asarray(mix, jnp.float32)
    C = mix.shape[0]
    leaves = jax.tree.leaves(stacked)
    C_loc = leaves[0].shape[0]
    i = jax.lax.axis_index(axis)
    cols = jax.lax.dynamic_slice_in_dim(mix, i * C_loc, C_loc, axis=1)

    def mixleaf(p):
        flat = p.astype(jnp.float32).reshape(C_loc, -1)
        full = jax.lax.psum(cols @ flat, axis)            # (C, n)
        out = jax.lax.dynamic_slice_in_dim(full, i * C_loc, C_loc, axis=0)
        return out.reshape(p.shape).astype(p.dtype)

    return jax.tree.map(mixleaf, stacked)


# ===========================================================================
# mesh-level (inside shard_map) operators — pod-scale FL
# ===========================================================================

def _wavg_psum(params, weight, axes):
    """Weighted mean over mesh axes: psum(w*theta)/psum(w)."""
    total_w = jax.lax.psum(weight, axes)
    return jax.tree.map(
        lambda p: (jax.lax.psum(p.astype(jnp.float32) * weight, axes)
                   / total_w).astype(p.dtype),
        params)


def mesh_hfl(params, weight, *, client_axis="data",
             num_groups: int = 2, pod_axis: Optional[str] = None):
    """Two-tier hierarchical aggregation.

    Single-pod: tier 1 over `axis_index_groups` partitions of the client
    axis, tier 2 over the full client axis. Multi-pod: tier 1 over the
    intra-pod client axis, tier 2 over the pod axis — the exact
    clients -> group-server -> global-server schedule of paper Fig. 1.
    """
    if pod_axis is not None:
        group = _wavg_psum(params, weight, client_axis)          # tier 1
        gw = jax.lax.psum(weight, client_axis)
        return jax.tree.map(                                      # tier 2
            lambda p: (jax.lax.psum(p.astype(jnp.float32) * gw, pod_axis)
                       / jax.lax.psum(gw, pod_axis)).astype(p.dtype),
            group)

    groups = topology.mesh_axis_groups(jax.lax.axis_size(client_axis),
                                       num_groups)
    # tier 1: group-server aggregate — a psum over each partition
    gw = jax.lax.psum(weight, client_axis, axis_index_groups=groups)
    group = jax.tree.map(
        lambda p: (jax.lax.psum(p.astype(jnp.float32) * weight,
                                client_axis, axis_index_groups=groups)
                   / gw).astype(p.dtype),
        params)
    # tier 2: global-server aggregate over group models. Each group model
    # is replicated across its (equal-size) group, so the gw-weighted sum
    # over the full axis overcounts numerator AND denominator by exactly
    # the group size — the factors cancel and this is the correct
    # group-weight-weighted mean (pinned against host `hfl_aggregate` in
    # test_fl_mesh_dryrun.py::test_mesh_hfl_matches_host).
    return jax.tree.map(
        lambda p: (jax.lax.psum(p.astype(jnp.float32) * gw, client_axis)
                   / jax.lax.psum(gw, client_axis) ).astype(p.dtype),
        group)


def mesh_afl_fedavg(params, weight, participate, *, client_axis="data",
                    pod_axis: Optional[str] = None):
    """Masked FedAvg over sampled participants. Non-participants keep the
    aggregate too (they would fetch it lazily in a real deployment; at pod
    scale every device holds the consensus model after the collective)."""
    axes = (client_axis,) if pod_axis is None else (client_axis, pod_axis)
    m = participate.astype(jnp.float32) * weight
    return _wavg_psum(params, m, axes)


def mesh_afl_gossip(params, *, client_axis="data", steps: int = 1):
    """Ring gossip: each client averages with its +-1 ring neighbors via
    collective_permute — O(2 * |params|) link traffic per step, no global
    collective. Iterating converges to the consensus mean."""
    n = jax.lax.axis_size(client_axis)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]

    def one_step(p):
        def mix(x):
            x32 = x.astype(jnp.float32)
            left = jax.lax.ppermute(x32, client_axis, perm=fwd)
            right = jax.lax.ppermute(x32, client_axis, perm=bwd)
            return ((x32 + left + right) / 3.0).astype(x.dtype)
        return jax.tree.map(mix, p)

    for _ in range(steps):
        params = one_step(params)
    return params


def mesh_cfl(params, global_params, weight, alpha, *, client_axis="data",
             pod_axis: Optional[str] = None):
    """Continual merge at pod scale: the federation mean is folded into
    each client's evolving model with rate alpha (EMA of the consensus),
    and the running global model is updated likewise. Returns
    (new_client_params, new_global_params)."""
    axes = (client_axis,) if pod_axis is None else (client_axis, pod_axis)
    mean = _wavg_psum(params, weight, axes)
    new_global = jax.tree.map(
        lambda g, m: ((1 - alpha) * g.astype(jnp.float32)
                      + alpha * m.astype(jnp.float32)).astype(g.dtype),
        global_params, mean)
    new_client = jax.tree.map(
        lambda c, g: ((1 - alpha) * c.astype(jnp.float32)
                      + alpha * g.astype(jnp.float32)).astype(c.dtype),
        params, new_global)
    return new_client, new_global
