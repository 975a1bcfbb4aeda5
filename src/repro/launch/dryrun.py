import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any jax-importing module: jax locks
# the host platform device count at first init, and the dry-run needs 512
# placeholder CPU devices to build the production meshes. It is pinned
# to the CPU so that on a machine with an accelerator it neither builds
# its meshes from the chips nor takes a chip another process holds.
# Everything else (tests, benches, examples) sees the real devices.

"""Multi-pod AOT dry-run: lower + compile every (architecture x input
shape) on the production meshes, and derive the roofline terms from the
compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --fl hfl --arch phi3-mini-3.8b

Results are cached as JSON under experiments/dryrun/.
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import combos, get_config
from repro.launch import roofline as rl
from repro.launch import serve as serve_mod
from repro.launch import train as train_mod
from repro.launch.mesh import make_fl_mesh, make_production_mesh
from repro.models.model import build_model
from repro.optim import optimizers
from repro.sharding import specs as sh


# dry-run defaults: the online-softmax (chunked) attention and chunked
# mLSTM are the production TPU paths (what the Pallas kernels implement);
# the quadratic einsum forms are the naive baselines, selectable for the
# §Perf before/after comparisons via --opt attn_impl=einsum etc.
DEFAULT_OVERRIDES = {"attn_impl": "chunked", "mlstm_impl": "chunked"}


def _apply_overrides(cfg, opts: Optional[str]):
    cfg = cfg.with_updates(**DEFAULT_OVERRIDES)
    if not opts:
        return cfg
    upd = {}
    for kv in opts.split(","):
        k, v = kv.split("=")
        field = {f.name: f for f in dataclasses.fields(cfg)}[k]
        if field.type in ("bool", bool):
            upd[k] = v.lower() in ("1", "true")
        elif field.type in ("int", int):
            upd[k] = int(v)
        elif field.type in ("float", float):
            upd[k] = float(v)
        else:
            upd[k] = v
    return cfg.with_updates(**upd)


def _sds_tree(tree):
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


# ---------------------------------------------------------------------------
# scan-cost extrapolation
#
# XLA's cost_analysis counts a lax.scan body ONCE (not x trip count), so a
# scanned-layer model under-reports FLOPs/bytes/collectives by ~num_layers.
# Full unrolled compiles are intractable on this host for 64-layer archs, so
# we lower two SHALLOW UNROLLED variants (depths p and 2p, where p is the
# arch's layer-pattern period) and fit   cost(L) = fixed + L/p * per_period.
# Decode shapes are natively unrolled and need no correction.
# ---------------------------------------------------------------------------

def _pattern_period(cfg) -> int:
    if cfg.shared_attn_every:
        return cfg.shared_attn_every
    if cfg.global_every:
        return cfg.global_every
    return 1


def is_homoish(cfg) -> bool:
    """Scan-cost extrapolation applies when layers repeat with a period."""
    kinds = set(cfg.layer_kinds())
    return kinds in ({"attn"}, {"mamba"})


def _depth_variant(cfg, depth: int):
    upd = {"num_layers": depth, "scan_layers": False, "remat": False}
    if cfg.block_pattern:
        upd["block_pattern"] = cfg.block_pattern[:depth]
    if cfg.encoder_layers:
        upd["encoder_layers"] = depth
    return cfg.with_updates(**upd)


def _extrapolate_costs(cfg, mesh, build_lowered, verbose=True):
    """Returns (flops, bytes, collective_bytes, collective_count) per device
    extrapolated to the full depth from two shallow unrolled compiles."""
    p = _pattern_period(cfg)
    d1, d2 = p, 2 * p
    L = cfg.num_layers
    pts = {}
    for d in (d1, d2):
        c = build_lowered(_depth_variant(cfg, d)).compile()
        cost = c.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        coll = rl.parse_collective_bytes(c.as_text())
        pts[d] = (float(cost.get("flops", 0.0)),
                  float(cost.get("bytes accessed", 0.0)),
                  coll["total"], coll["count"])
    per_period = tuple((b - a) / 1.0 for a, b in zip(pts[d1], pts[d2]))
    fixed = tuple(a - pp for a, pp in zip(pts[d1], per_period))
    n_periods = L / p
    out = tuple(f + n_periods * pp for f, pp in zip(fixed, per_period))
    if verbose:
        print(f"  scan-cost extrapolation: depths ({d1},{d2}) -> L={L} "
              f"(period {p}); flops/dev {out[0]/1e12:.2f}T")
    return out


def lower_and_compile(arch: str, shape_name: str, *, multi_pod=False,
                      opts: Optional[str] = None, verbose=True
                      ) -> Dict[str, Any]:
    cfg = _apply_overrides(get_config(arch), opts)
    sh.set_profile(cfg.sharding_profile)
    sh.set_seq_shardable(set(cfg.layer_kinds()) == {"attn"})
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.perf_counter()

    import math as _math

    def _lower_step(cfg_v):
        """Lower the shape-appropriate step for a config variant."""
        model_v = build_model(cfg_v)
        params_shape = jax.eval_shape(model_v.init, jax.random.PRNGKey(0))
        p_shardings = sh.tree_shardings(params_shape, mesh)
        params_sds = jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            params_shape, p_shardings)
        if shape.kind == "train":
            opt = optimizers.adamw(1e-4)
            opt_shape = jax.eval_shape(opt.init, params_shape)
            _, o_sh = train_mod.train_state_shardings(
                params_shape, opt_shape, mesh)
            opt_sds = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                opt_shape, o_sh)
            batch_specs = model_v.train_batch_specs(shape.global_batch,
                                                    shape.seq_len)
            b_sh = train_mod.batch_shardings(batch_specs, mesh)
            batch_sds = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                batch_specs, b_sh)
            step = train_mod.make_train_step(model_v, opt)
            return jax.jit(step, donate_argnums=(0, 1)).lower(
                params_sds, opt_sds, batch_sds)
        elif shape.kind == "prefill":
            batch_specs = model_v.train_batch_specs(shape.global_batch,
                                                    shape.seq_len)
            batch_specs.pop("labels")
            b_sh = train_mod.batch_shardings(batch_specs, mesh)
            batch_sds = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                batch_specs, b_sh)
            step = serve_mod.make_prefill_step(model_v)
            return jax.jit(step).lower(params_sds, batch_sds)
        else:  # decode
            state_shape = model_v.decode_state_specs(shape.global_batch,
                                                     shape.seq_len)
            st_sh = serve_mod.decode_state_shardings(state_shape, mesh, cfg_v)
            state_sds = jax.tree.map(
                lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
                state_shape, st_sh)
            tok_spec = model_v.decode_token_specs(shape.global_batch)
            tok_sds = jax.ShapeDtypeStruct(
                tok_spec.shape, tok_spec.dtype,
                sharding=serve_mod.token_shardings(tok_spec, mesh))
            step = serve_mod.make_serve_step(model_v)
            return jax.jit(step, donate_argnums=(1,)).lower(
                params_sds, state_sds, tok_sds)

    model = build_model(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n_params = sum(_math.prod(l.shape) for l in jax.tree.leaves(params_shape))
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind in ("train", "prefill") else shape.global_batch)
    flops_factor = 6.0 if shape.kind == "train" else 2.0

    with jax.sharding.set_mesh(mesh):
        lowered = _lower_step(cfg)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

        roof = rl.analyze(compiled, chips)
        scan_corrected = False
        if (shape.kind in ("train", "prefill") and cfg.scan_layers
                and is_homoish(cfg)):
            try:
                fl_, by_, cb_, cc_ = _extrapolate_costs(
                    cfg, mesh, _lower_step, verbose=verbose)
                # the grad-accumulation scan body is also counted once by
                # cost_analysis; everything except the optimizer update
                # lives inside it, so scale by the microbatch count
                ac = max(1, cfg.grad_accum) if shape.kind == "train" else 1
                roof.flops_per_device = fl_ * ac
                roof.bytes_per_device = by_ * ac
                roof.collective_bytes_per_device = cb_ * ac
                roof.collective_count = int(cc_ * ac)
                scan_corrected = True
            except Exception as e:
                print(f"  (scan-cost extrapolation failed: {e})")
    n_active = rl.active_param_count(cfg, n_params)
    model_flops = flops_factor * n_active * tokens

    mem = compiled.memory_analysis()
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "opts": opts or "",
        "kind": shape.kind,
        "params": int(n_params), "active_params": int(n_active),
        "model_flops_total": float(model_flops),
        "model_flops_per_device": float(model_flops / chips),
        "scan_cost_corrected": scan_corrected,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes": int(mem.argument_size_in_bytes
                              + mem.temp_size_in_bytes),
        },
        "roofline": roof.to_dict(),
        "useful_flops_ratio": float(model_flops / chips
                                    / max(1.0, roof.flops_per_device)),
        "ok": True,
    }
    if verbose:
        r = result["roofline"]
        print(f"[{arch} x {shape_name} x {result['mesh']}"
              f"{' ' + opts if opts else ''}]")
        print(f"  params={n_params/1e9:.2f}B active={n_active/1e9:.2f}B "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s")
        print(f"  per-device: flops={r['flops_per_device']/1e12:.3f}T "
              f"bytes={r['bytes_per_device']/1e9:.2f}GB "
              f"coll={r['collective_bytes_per_device']/1e9:.3f}GB "
              f"({r['collective_count']} ops)")
        print(f"  terms: compute={r['compute_s']*1e3:.2f}ms "
              f"memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms "
              f"-> {r['dominant']}-bound")
        print(f"  hbm peak/device={result['memory']['peak_bytes']/1e9:.2f}GB "
              f"useful-flops-ratio={result['useful_flops_ratio']:.2f}")
    return result


# ---------------------------------------------------------------------------
# FL dry-run: lower fl_train_step per aggregation strategy
# ---------------------------------------------------------------------------

def lower_fl(arch: str, strategy: str, *, multi_pod=False, seq_len=512,
             per_client_batch=4, local_steps=1, afl_mode="fedavg",
             verbose=True):
    from repro.core.fl_types import FLConfig
    from repro.core.trainer import (FederatedTrainer, fl_tree_shardings,
                                    fl_tree_shardings_opt)

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    clients = (mesh.shape["data"] * mesh.shape.get("pod", 1)
               if multi_pod else mesh.shape["data"])
    fl = FLConfig(strategy=strategy, num_clients=clients,
                  num_groups=2 if not multi_pod else mesh.shape["pod"],
                  local_steps=local_steps, lr=0.01, afl_mode=afl_mode)
    model = build_model(cfg)
    trainer = FederatedTrainer(model, fl, mesh)

    t0 = time.perf_counter()
    state_shape = jax.eval_shape(trainer.init_state, jax.random.PRNGKey(0))
    shardings = {
        "client_params": fl_tree_shardings(state_shape["client_params"], mesh),
        "opt": fl_tree_shardings_opt(state_shape["opt"], mesh),
        "round": NamedSharding(mesh, P()),
    }
    if "global_params" in state_shape:
        shardings["global_params"] = sh.tree_shardings(
            state_shape["global_params"], mesh)
    state_sds = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        state_shape, shardings)

    batch_specs = trainer.fl_batch_specs(seq_len, per_client_batch)
    ca = ("pod", "data") if multi_pod else ("data",)
    b_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, sh.fit_spec(
            s.shape, P(ca if len(ca) > 1 else ca[0]), mesh)), batch_specs)
    batch_sds = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        batch_specs, b_sh)
    w_sds = jax.ShapeDtypeStruct((clients,), jnp.float32)
    part_sds = jax.ShapeDtypeStruct((clients,), jnp.bool_)

    with jax.sharding.set_mesh(mesh):
        lowered = jax.jit(trainer.fl_train_step, donate_argnums=(0,)).lower(
            state_sds, batch_sds, w_sds, part_sds)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    roof = rl.analyze(compiled, chips)
    mem = compiled.memory_analysis()
    result = {
        "arch": arch,
        "fl_strategy": (strategy if afl_mode == "fedavg"
                        else f"{strategy}-{afl_mode}"),
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "clients": clients,
        "seq_len": seq_len, "per_client_batch": per_client_batch,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {"peak_bytes": int(mem.argument_size_in_bytes
                                     + mem.temp_size_in_bytes)},
        "roofline": roof.to_dict(),
        "ok": True,
    }
    if verbose:
        r = result["roofline"]
        print(f"[FL {strategy} x {arch} x {result['mesh']} "
              f"clients={clients}]")
        print(f"  lower={t_lower:.1f}s compile={t_compile:.1f}s "
              f"coll={r['collective_bytes_per_device']/1e9:.3f}GB/dev "
              f"({r['collective_count']} collective ops) "
              f"-> {r['dominant']}-bound "
              f"hbm={result['memory']['peak_bytes']/1e9:.2f}GB")
    return result


# ---------------------------------------------------------------------------

def _out_path(outdir, result, tag=""):
    if "fl_strategy" in result:
        name = f"fl_{result['fl_strategy']}_{result['arch']}_{result['mesh']}"
    else:
        name = f"{result['arch']}_{result['shape']}_{result['mesh']}"
    if tag:
        name += f"_{tag}"
    return os.path.join(outdir, name.replace("/", "-") + ".json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--fl", choices=["hfl", "afl", "cfl"])
    ap.add_argument("--fl-mode", default="fedavg",
                    choices=["fedavg", "gossip"])
    ap.add_argument("--fl-local-steps", type=int, default=1)
    ap.add_argument("--opt", help="cfg overrides k=v,k=v (hillclimbing)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}
    jobs = []
    if args.fl:
        jobs = [("fl", args.arch, args.fl, mp) for mp in meshes[args.mesh]]
    elif args.all:
        for a, s in combos():
            for mp in meshes[args.mesh]:
                jobs.append(("std", a, s, mp))
    else:
        for mp in meshes[args.mesh]:
            jobs.append(("std", args.arch, args.shape, mp))

    failures = 0
    for job in jobs:
        kind, arch = job[0], job[1]
        # skip combos already completed (JSON cache), unless --force
        if kind == "fl":
            fs = job[2] if args.fl_mode == "fedavg" else f"{job[2]}-{args.fl_mode}"
            probe = {"arch": arch, "fl_strategy": fs,
                     "mesh": "2x16x16" if job[3] else "16x16"}
        else:
            probe = {"arch": arch, "shape": job[2],
                     "mesh": "2x16x16" if job[3] else "16x16"}
        ppath = _out_path(args.out, probe, args.tag)
        if not args.force and os.path.exists(ppath):
            try:
                with open(ppath) as f:
                    if json.load(f).get("ok"):
                        print(f"skip (cached): {ppath}", flush=True)
                        continue
            except Exception:
                pass
        try:
            if kind == "fl":
                result = lower_fl(arch, job[2], multi_pod=job[3],
                                  afl_mode=args.fl_mode,
                                  local_steps=args.fl_local_steps)
            else:
                result = lower_and_compile(arch, job[2], multi_pod=job[3],
                                           opts=args.opt)
        except Exception as e:
            traceback.print_exc()
            result = {"arch": arch, "ok": False, "error": str(e)[:2000],
                      "shape": job[2] if kind == "std" else "",
                      "fl_strategy": job[2] if kind == "fl" else None,
                      "mesh": "2x16x16" if job[3] else "16x16"}
            if result["fl_strategy"] is None:
                result.pop("fl_strategy")
            failures += 1
        path = _out_path(args.out, result, args.tag)
        if result.get("ok") or not os.path.exists(path) or args.force:
            with open(path, "w") as f:
                json.dump(result, f, indent=1)
        print(f"  -> {path}\n", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
