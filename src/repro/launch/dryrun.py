"""Multi-pod dry-run: prove the distribution config lowers + compiles for
every (architecture x input shape x mesh) without hardware.

MUST set the fake-device flag before any other import (jax locks device
count on first init).

Usage:
  python -m repro.launch.dryrun --arch qwen2_5_7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out-dir results/dryrun
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_FLAGS",
                           "--xla_force_host_platform_device_count=512"))
os.environ["JAX_PLATFORMS"] = "cpu"      # fake devices; never the chip

# ruff: noqa: E402
import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import INPUT_SHAPES
from repro.distributed.sharding import (batch_pspecs, cache_pspecs, dp_axes,
                                        state_pspecs, to_named, tree_pspecs)
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs, params_struct, state_struct
from repro.launch.steps import make_prefill_step, make_serve_step, \
    make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P

# hardware constants (TPU v5e-class target; see EXPERIMENTS.md §Roofline)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link per chip

COLLECTIVE_RE = re.compile(
    r"^\s*(?:%|\S+ = )?"
    r"(?P<shape>\(?[a-z0-9]+\[[0-9,]*\][^ ]*\)?)\s+"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)", re.M)

SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
               "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in SHAPE_RE.findall(shape_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes_from_hlo(hlo: str, loop_mult: int) -> dict:
    """Sum output bytes of collective ops. Ops inside while-loop bodies
    (the layer scan) are multiplied by ``loop_mult`` — a documented
    approximation (the only while loops in these steps are layer stacks).
    """
    per_op = {}
    total = 0.0
    # split into computations; while bodies are named *body*
    comps = re.split(r"\n(?=[%\w].*\{)", hlo)
    for comp in comps:
        header = comp.split("\n", 1)[0]
        in_loop = ("body" in header) or ("while" in header)
        mult = loop_mult if in_loop else 1
        for m in COLLECTIVE_RE.finditer(comp):
            b = _shape_bytes(m.group("shape")) * mult
            per_op[m.group("op")] = per_op.get(m.group("op"), 0) + b
            total += b
    per_op["total"] = total
    return per_op


def build_lowered(arch: str, shape_name: str, mesh, *, overrides=None,
                  kv_seq_shard=False):
    import dataclasses as _dc
    cfg = get_config(arch)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    kind, specs = input_specs(cfg, shape_name)

    if kind == "train":
        step = make_train_step(cfg)
        state = state_struct(cfg)
        st_sh = to_named(state_pspecs(state, cfg, mesh), mesh)
        b_sh = to_named(batch_pspecs(specs["batch"], cfg, mesh), mesh)
        fn = jax.jit(step, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, None))
        with mesh:
            return fn.lower(state, specs["batch"]), cfg

    params = params_struct(cfg)
    p_sh = to_named(tree_pspecs(params, cfg, mesh), mesh)

    if kind == "prefill":
        step = make_prefill_step(cfg)
        b_sh = to_named(batch_pspecs(specs["batch"], cfg, mesh), mesh)
        fn = jax.jit(step, in_shardings=(p_sh, b_sh))
        with mesh:
            return fn.lower(params, specs["batch"]), cfg

    # decode
    step = make_serve_step(cfg, ring=specs["ring"])
    B = specs["token"].shape[0]
    c_sh = to_named(cache_pspecs(specs["cache"], cfg, mesh, batch=B,
                                 kv_seq_shard=kv_seq_shard), mesh)
    dp = dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in
                        (dp if isinstance(dp, tuple) else (dp,))]))
    tok_spec = P(dp) if B % n_dp == 0 and B > 1 else P()
    t_sh = NamedSharding(mesh, tok_spec)
    fn = jax.jit(step, in_shardings=(p_sh, c_sh, t_sh, t_sh),
                 out_shardings=(None, c_sh))
    with mesh:
        return fn.lower(params, specs["cache"], specs["token"],
                        specs["pos"]), cfg


def model_flops(cfg, shape_name: str) -> float:
    """6·N_active·D (training) / 2·N_active·D (per-token inference) — the
    'useful' MFU-accounting FLOPs."""
    shp = INPUT_SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if shp.kind == "train":
        return 6.0 * n_active * shp.global_batch * shp.seq_len
    if shp.kind == "prefill":
        return 2.0 * n_active * shp.global_batch * shp.seq_len
    return 2.0 * n_active * shp.global_batch  # decode: one token per seq


def run_one(arch: str, shape_name: str, mesh_kind: str, *, overrides=None,
            mesh_shape=None, kv_seq_shard=False) -> dict:
    from repro.core.planner.cost_model import HW, roofline_terms

    if mesh_shape:  # hillclimb meshes, e.g. "32x8"
        dims = [int(x) for x in mesh_shape.split("x")]
        axes = ("pod", "data", "model")[-len(dims):]
        mesh = jax.make_mesh(tuple(dims), axes)
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "pod"))
    n_chips = mesh.devices.size
    cfg = get_config(arch)
    if shape_name == "long_500k" and not cfg.supports_long_decode:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped",
                "reason": "enc-dec (448 decoder positions); see DESIGN.md"}

    t0 = time.time()
    lowered, cfg = build_lowered(arch, shape_name, mesh,
                                 overrides=overrides,
                                 kv_seq_shard=kv_seq_shard)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    # NOTE: on the CPU backend cost_analysis() counts while-loop (layer
    # scan) bodies ONCE, so these raw values undercount; the roofline uses
    # the analytic cost model (planner §4.3) — see EXPERIMENTS.md §Roofline.
    flops_raw = float(cost.get("flops", 0.0))
    bytes_raw = float(cost.get("bytes accessed", 0.0))

    hlo = compiled.as_text()
    loop_mult = max(1, cfg.num_layers // (len(cfg.rglru_block_pattern)
                    if cfg.arch_type == "hybrid" else 1))
    coll_hlo = collective_bytes_from_hlo(hlo, loop_mult)

    mesh_shape_d = {a: int(mesh.shape[a]) for a in mesh.axis_names}
    rt = roofline_terms(cfg, shape_name, mesh_shape_d,
                        kv_seq_shard=kv_seq_shard)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok", "n_chips": int(n_chips),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        # compiled-artifact evidence
        "hlo_flops_raw": flops_raw, "hlo_bytes_raw": bytes_raw,
        "hlo_collective_bytes": coll_hlo,
        "hlo_collective_ops": {k: v for k, v in coll_hlo.items()
                               if k != "total"},
        # analytic roofline (planner cost model)
        "flops": rt["flops"],
        "hbm_bytes_per_chip": rt["hbm_bytes_per_chip"],
        "collective_bytes_per_chip": rt["collective_bytes_per_chip"],
        "t_compute": rt["t_compute"], "t_memory": rt["t_memory"],
        "t_collective": rt["t_collective"],
        "bottleneck": rt["bottleneck"],
        "model_flops": model_flops(cfg, shape_name),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    rec["useful_flops_ratio"] = rec["model_flops"] / max(rt["flops"], 1.0)
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                rec[attr] = int(v)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "pod"])
    ap.add_argument("--mesh-shape", default=None,
                    help="hillclimb mesh, e.g. 32x8 (data x model)")
    ap.add_argument("--set", action="append", default=[],
                    help="config override, e.g. --set ssm_chunk=256")
    ap.add_argument("--kv-seq-shard", action="store_true",
                    help="shard decode KV cache sequence dim over 'model'")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = int(v) if v.lstrip("-").isdigit() else v
    rec_args = dict(arch=args.arch, shape_name=args.shape,
                    mesh_kind=args.mesh, overrides=overrides or None,
                    mesh_shape=args.mesh_shape,
                    kv_seq_shard=args.kv_seq_shard)
    try:
        rec = run_one(**rec_args)
    except Exception as e:  # record the failure — these are bugs to fix
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-2000:]}
    out = json.dumps(rec, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(out)
    print(out)
    return 0 if rec.get("status") in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
