"""Partition-spec rules: map parameter paths and activations to mesh axes.

Conventions
-----------
* mesh axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.
* FSDP axis = ("pod","data") when present, else ("data",)  — weights' first
  shardable dim is sharded over it; tensor-parallel dim over "model".
* Activations: batch over FSDP axis, hidden features over "model" where the
  dimension divides.

`fit_spec` drops any mesh axis that does not evenly divide the corresponding
dim, which keeps every architecture lowerable regardless of odd vocab /
head-count sizes (e.g. seamless vocab=256206).
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from typing import Sequence

import jax
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# sharding profiles
#   "tp" (default) — FSDP over ("pod","data") + tensor-parallel over "model".
#   "dp"           — pure data parallel: batch over ALL mesh axes, params
#                    replicated. The right profile for small archs (e.g.
#                    xlstm-125m) where TP=16 makes every layer boundary a
#                    collective and params/chip are tiny anyway.
#   "fsdp"         — flat fully-sharded data parallel: batch AND parameters
#                    sharded over all mesh axes (256/512-way); no tensor
#                    parallelism. The right profile for big dense archs at
#                    train_4k, where per-device batch under tp (16 seqs)
#                    blows activation memory and TP boundary collectives
#                    dominate.
# ---------------------------------------------------------------------------

_PROFILE = contextvars.ContextVar("sharding_profile", default="tp")
_SEQ_SHARDABLE = contextvars.ContextVar("seq_shardable", default=True)


def set_seq_shardable(flag: bool):
    """Sequence (context-parallel) sharding is only valid for attention
    stacks; recurrent blocks (Mamba2/xLSTM) scan sequentially over the
    sequence, and sharding it forces a reshard per chunk."""
    _SEQ_SHARDABLE.set(bool(flag))


def set_profile(profile: str):
    assert profile in ("tp", "dp", "fsdp", "moe"), profile
    _PROFILE.set(profile)


def get_profile() -> str:
    return _PROFILE.get()


@contextlib.contextmanager
def profile_ctx(profile: str):
    tok = _PROFILE.set(profile)
    try:
        yield
    finally:
        _PROFILE.reset(tok)


def axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= axis_size(mesh, a)
        return n
    try:
        return mesh.shape[axis]
    except Exception:
        return 1


def fit_spec(shape: Sequence[int], spec: P, mesh) -> P:
    """Zero out spec entries whose mesh-axis size does not divide the dim."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, ax in zip(shape, entries):
        if ax is None:
            out.append(None)
            continue
        if dim % max(1, axis_size(mesh, ax)) == 0:
            out.append(ax)
        elif isinstance(ax, (tuple, list)):
            # try progressively smaller prefixes of a compound axis
            kept = None
            for i in range(len(ax) - 1, 0, -1):
                sub = tuple(ax[:i])
                if dim % max(1, axis_size(mesh, sub)) == 0:
                    kept = sub
                    break
            out.append(kept)
        else:
            out.append(None)
    return P(*out)


def fsdp_axes(mesh):
    names = mesh.axis_names
    if "pod" in names:
        return ("pod", "data")
    return ("data",)


def batch_axes(mesh):
    """Mesh axes carrying the batch dim.

    dp/fsdp single-pod: all axes (flat data parallelism). Multi-pod, the
    global batch (256) cannot divide 512 chips, so: fsdp shards batch over
    ("pod","data") and the SEQUENCE dim over "model" (context parallel);
    dp shards batch over ("data","model") with the pod axis carrying only
    gradient synchronization (params are replicated anyway)."""
    prof = get_profile()
    multi = "pod" in mesh.axis_names
    if prof in ("fsdp", "moe"):
        return ("pod", "data") if multi else ("data", "model")
    if prof == "dp":
        return ("data", "model")
    return fsdp_axes(mesh)


def seq_axis(mesh):
    """Mesh axis for the sequence dim of (B, S, ...) activations, if any.
    Only the fsdp profile context-parallelizes; under moe the "model"
    axis is reserved for experts (sharing it with the sequence dim made
    every MoE layer boundary a full reshard)."""
    if (get_profile() == "fsdp" and "pod" in mesh.axis_names
            and _SEQ_SHARDABLE.get()):
        return "model"
    return None


# ---------------------------------------------------------------------------
# parameter rules: (regex on param path) -> spec template
# templates use "F" for the FSDP compound axis and "M" for model axis.
# First match wins; rank-adjusted and divisibility-fitted afterwards.
# ---------------------------------------------------------------------------

_RULES = [
    # embeddings (vocab, d): vocab over "model" so tied-unembed logits come
    # out vocab-sharded without resharding (lookup lowers to one-hot psum);
    # d replicated — embed tables are small relative to the layer stack.
    (r"embed$", ("M", None)),
    (r"unembed/kernel$", (None, "M")),
    # attention projections stored fused 2-D: (d, H*dh) / (H*dh, d)
    (r"(wq|wk|wv|wq_a|wq_b|w_dkv|w_uk|w_uv|w_kpe)/kernel$", ("F", "M")),
    (r"wo/kernel$", ("M", "F")),
    # mlp
    (r"(wi_gate|wi_up)$", ("F", "M")),
    (r"wo$", ("M", "F")),
    (r"wi/kernel$", ("F", "M")),
    # moe experts: (E, d, f) / (E, f, d)  — experts over model axis
    (r"experts_(gate|up)$", ("M", "F", None)),
    (r"experts_down$", ("M", None, "F")),
    (r"router/kernel$", ("F", None)),
    # mamba / ssm: in_proj (d, inner*...), out_proj (inner, d)
    (r"(in_proj|out_proj|x_proj|dt_proj|z_proj)/kernel$", ("F", "M")),
    (r"conv1d$", (None, "M")),
    (r"(A_log|D|dt_bias)$", ("M",)),
    # xlstm
    (r"(wq|wk|wv|wi|wf|wo_gate|up_proj|down_proj|w_cell)$", ("F", "M")),
    # cnn
    (r"conv\d/kernel$", (None, None, None, "M")),
    # norms / scalars / biases: replicate
    (r"(scale|bias)$", ()),
]


_EXPERT_PAT = re.compile(r"experts_(gate|up|down)$")


def spec_for_param(path: str, shape, mesh) -> P:
    if get_profile() == "dp":
        return P()                        # replicate all params
    if get_profile() in ("fsdp", "moe"):
        if not shape:
            return P()
        if re.search(r"embed$", path):
            # keep vocab over "model" so tied-unembed logits stay sharded
            return fit_spec(shape, P("model", None), mesh)
        if re.search(r"unembed/kernel$", path):
            return fit_spec(shape, P(None, "model"), mesh)
        if get_profile() == "moe" and _EXPERT_PAT.search(path):
            # true expert parallelism: experts stay sharded over "model"
            # (the dispatch/combine einsums become an all-to-all instead
            # of FSDP-gathering every expert's weights each layer)
            fa2 = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
            tmpl = (("model",) + (fa2 if len(fa2) > 1 else (fa2[0],))
                    + (None,) * (len(shape) - 2))
            return fit_spec(shape, P(*tmpl), mesh)
        big = max(range(len(shape)), key=lambda i: shape[i])
        entries = [None] * len(shape)
        entries[big] = tuple(mesh.axis_names)
        return fit_spec(shape, P(*entries), mesh)
    fa = fsdp_axes(mesh)
    for pat, tmpl in _RULES:
        if re.search(pat, path):
            entries = []
            for t in tmpl[: len(shape)]:
                if t == "F":
                    entries.append(fa if len(fa) > 1 else fa[0])
                elif t == "M":
                    entries.append("model")
                else:
                    entries.append(t)
            entries += [None] * (len(shape) - len(entries))
            return fit_spec(shape, P(*entries), mesh)
    # default: shard the largest dim over FSDP if it divides
    if shape:
        big = max(range(len(shape)), key=lambda i: shape[i])
        entries = [None] * len(shape)
        entries[big] = fa if len(fa) > 1 else fa[0]
        return fit_spec(shape, P(*entries), mesh)
    return P()


_STACKED_RE = re.compile(r"(^|/)layers/")


def tree_specs(params, mesh, prefix=""):
    """Build a pytree of PartitionSpecs parallel to `params`.

    Parameters under a `layers/` path are scan-stacked with a leading
    num_layers dim: the per-layer rules apply to shape[1:] and the stack
    dim stays unsharded (each scan step slices one layer; sharding the
    stack dim would turn every slice into a broadcast-gather and — worse —
    misalign expert/TP dims by one position)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path
        )
        full = prefix + pstr
        if _STACKED_RE.search(full) and leaf.ndim >= 2:
            inner = spec_for_param(full, leaf.shape[1:], mesh)
            specs.append(fit_spec(leaf.shape, P(None, *inner), mesh))
        else:
            specs.append(spec_for_param(full, leaf.shape, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


def tree_shardings(params, mesh, prefix=""):
    from jax.sharding import NamedSharding
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_specs(params, mesh, prefix)
    )


# activation specs -----------------------------------------------------------

def act_spec_btd(mesh) -> P:
    """(batch, seq, d) activations."""
    ba = batch_axes(mesh)
    if get_profile() in ("dp", "fsdp"):
        return P(ba if len(ba) > 1 else ba[0], seq_axis(mesh), None)
    return P(ba if len(ba) > 1 else ba[0], None, "model")


def batch_spec(mesh) -> P:
    ba = batch_axes(mesh)
    return P(ba if len(ba) > 1 else ba[0])


# client-axis specs (mesh-sharded fused executor, DESIGN.md §11) ------------
# The fused executor's pytrees carry a LEADING CLIENT AXIS (stacked
# federation params / dataset / per-round schedule tensors). Under the
# 1-D client mesh (`launch.mesh.make_client_mesh`) that axis — and only
# that axis — is partitioned over "data"; parameters within one client
# stay whole (the paper CNN needs no model axis).

def client_stack_specs(tree, *, axis: str = "data", lead: int = 0):
    """Pytree of PartitionSpecs sharding dim `lead` of every leaf over
    `axis` (lead=0: stacked federation state (C, ...); lead=1: hoisted
    per-round scan inputs (rounds, C, ...)). Scalars/short leaves raise —
    a silent replicate here would hide a mis-sharded carry."""
    def spec(l):
        ndim = getattr(l, "ndim", None)
        if ndim is None or ndim <= lead:
            raise ValueError(
                f"client_stack_specs: leaf of ndim {ndim} cannot shard "
                f"dim {lead} over {axis!r}")
        entries = [None] * ndim
        entries[lead] = axis
        return P(*entries)
    return jax.tree.map(spec, tree)


def replicated_specs(tree):
    """Pytree of empty PartitionSpecs (fully replicated leaves)."""
    return jax.tree.map(lambda _: P(), tree)


def remap_act_spec(spec: P, mesh) -> P:
    """Translate a tp-profile activation spec to the active profile:
    under dp/fsdp, "data" (the batch dim) -> batch_axes(mesh), "model"
    (a feature dim) -> replicated; multi-pod fsdp additionally shards the
    sequence dim (position 1 of batch-first specs) over "model"."""
    prof = get_profile()
    if prof not in ("dp", "fsdp", "moe"):
        return spec
    if prof == "moe" and len(spec) and spec[0] == "model":
        return spec    # expert-parallel constraint (e over model): keep
    multi = "pod" in mesh.axis_names
    keep_model = prof == "moe" and multi   # "model" reserved for experts
    ba = batch_axes(mesh)
    out = []
    for i, e in enumerate(spec):
        if e == "data" or (isinstance(e, (tuple, list)) and "data" in e):
            out.append(ba)
        elif e == "model":
            out.append("model" if keep_model else None)
        else:
            out.append(e)
    sa = seq_axis(mesh)
    if sa and len(out) >= 2 and out[0] == ba and out[1] is None:
        out[1] = sa
    return P(*out)
