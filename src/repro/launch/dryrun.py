import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds the jitted step (train_step for train
shapes, prefill forward for prefill shapes, serve_step for decode shapes),
lowers it against ShapeDtypeStruct inputs under the production mesh, compiles
it, and records:

  * memory_analysis()  — per-device bytes (proves the sharding fits / where
    it doesn't, see EXPERIMENTS.md §Dry-run)
  * cost_analysis()    — HLO FLOPs + bytes accessed for §Roofline
  * collective bytes   — parsed from the optimized HLO text: operand bytes of
    all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute

Results go to benchmarks/results/dryrun/<cell>.json so the run is resumable
cell-by-cell (each cell can also run in a fresh subprocess via --subprocess,
isolating any single-cell failure).

Usage:
  python -m repro.launch.dryrun --arch gemma-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--subprocess]
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict

import numpy as np

RESULTS_DIR = os.path.join("benchmarks", "results", "dryrun")

# TPU v5e constants (per chip) for §Roofline
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_BW = 50e9  # per link

_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2, "u16": 2, "f8e4m3": 1,
    "f8e5m2": 1,
}

_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\w+\[[^\]]*\]|\([^)]*\)))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def parse_collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum output-shape bytes of every collective op in the optimized HLO."""
    out: Dict[str, float] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shapes_str, kind = m.group(1), m.group(2)
        total = 0
        for sm in _SHAPE_RE.finditer(shapes_str):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0) + total
    return out


def build_step(arch_name: str, shape_name: str, mesh, multi_pod: bool):
    """Returns (fn, example_args (ShapeDtypeStructs), in_shardings, donate)."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import SHAPES, get_arch
    from repro.dist.param_sharding import (
        batch_shardings,
        cache_shardings,
        param_shardings,
        state_shardings,
    )
    from repro.dist.sharding import default_rules, use_sharding
    from repro.models.model import (
        decode_step,
        init_params,
        input_specs,
        prefill,
    )
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import create_train_state, make_train_step

    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    specs = input_specs(cfg, shape)

    params_shape = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    p_sh = param_shardings(cfg, params_shape, mesh)

    if shape.kind == "train":
        opt_cfg = OptimizerConfig(
            total_steps=10_000,
            moment_dtype="bfloat16" if cfg.param_count() > 2e10 else "float32",
        )
        step = make_train_step(cfg, opt_cfg)
        state_shape = jax.eval_shape(
            lambda: create_train_state(cfg, opt_cfg, jax.random.key(0))
        )
        s_sh = state_shardings(cfg, state_shape, mesh)
        b_sh = batch_shardings(mesh, specs)
        fn = step
        args = (state_shape, specs)
        in_sh = (s_sh, b_sh)
    elif shape.kind == "prefill":
        def fn(params, batch):
            return prefill(cfg, params, batch, shape.seq_len)

        b_sh = batch_shardings(mesh, specs)
        args = (params_shape, specs)
        in_sh = (p_sh, b_sh)
    else:  # decode
        def fn(params, cache, tokens):
            return decode_step(cfg, params, cache, tokens)

        cache_shape = specs["cache"]
        c_sh = cache_shardings(cfg, cache_shape, mesh)
        t_sh = batch_shardings(mesh, specs["tokens"])
        args = (params_shape, cache_shape, specs["tokens"])
        in_sh = (p_sh, c_sh, t_sh)
    return fn, args, in_sh


def run_cell(
    arch_name: str, shape_name: str, mesh_kind: str, out_dir: str = RESULTS_DIR
) -> Dict[str, Any]:
    import jax

    from repro.dist.sharding import default_rules, use_sharding
    from repro.launch.mesh import make_production_mesh

    multi = mesh_kind == "multi"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi)
    result: Dict[str, Any] = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": list(mesh.devices.shape),
        "mesh_kind": mesh_kind,
        "n_devices": int(np.prod(mesh.devices.shape)),
    }
    try:
        fn, args, in_sh = build_step(arch_name, shape_name, mesh, multi)
        rules = default_rules(multi_pod=multi)
        with use_sharding(mesh, rules):
            jitted = jax.jit(fn, in_shardings=in_sh)
            lowered = jitted.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = parse_collective_bytes(hlo)
        from repro.launch.hlo_analysis import analyze_collectives

        coll_corr = analyze_collectives(hlo)

        def _get(obj, key):
            try:
                v = obj[key] if isinstance(obj, dict) else getattr(obj, key, None)
                return float(v) if v is not None else None
            except (TypeError, ValueError, KeyError, AttributeError):
                return None

        result.update(
            {
                "ok": True,
                "lower_s": round(t_lower - t0, 2),
                "compile_s": round(t_compile - t_lower, 2),
                "flops": _get(cost, "flops"),
                "bytes_accessed": _get(cost, "bytes accessed"),
                "transcendentals": _get(cost, "transcendentals"),
                "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_size_bytes": getattr(
                    mem, "generated_code_size_in_bytes", None
                ),
                "collective_bytes": coll,
                "collective_bytes_total": float(sum(coll.values())),
                # while-trip-count corrected (scan bodies execute L times but
                # appear once in the HLO text — see launch/hlo_analysis.py)
                "collective_bytes_corrected": coll_corr,
                "collective_bytes_corrected_total": float(sum(coll_corr.values())),
                "hlo_n_lines": hlo.count("\n"),
            }
        )
    except Exception as e:  # reprolint: allow(broad-except) recorded, not fatal to the sweep
        result.update(
            {
                "ok": False,
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
            }
        )
    result["total_s"] = round(time.time() - t0, 2)

    os.makedirs(out_dir, exist_ok=True)
    fname = os.path.join(out_dir, f"{arch_name}__{shape_name}__{mesh_kind}.json")
    with open(fname, "w") as f:
        json.dump(result, f, indent=2)
    return result


def run_cache_cell(mesh_kind: str, out_dir: str = RESULTS_DIR) -> Dict[str, Any]:
    """The paper's technique at datacenter scale: batched fractional OGB over
    a 2^30-item catalog sharded across the production mesh (one psum per
    bisection iteration).  Lower + compile + roofline terms, like any cell."""
    import jax
    import jax.numpy as jnp

    from repro.core.ogb import theoretical_eta
    from repro.jaxcache.sharded import make_sharded_step
    from repro.launch.mesh import make_production_mesh

    multi = mesh_kind == "multi"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi)
    N, C, B = 1 << 30, 1 << 24, 1 << 20  # 1.07B items, 16M cache, 1M reqs/step
    eta = theoretical_eta(C, N, 10_000 * B, B)
    result: Dict[str, Any] = {
        "arch": "ogb-cache-dataplane",
        "shape": f"N{N}_B{B}",
        "mesh_kind": mesh_kind,
        "n_devices": int(mesh.size),
    }
    try:
        step, f_sh = make_sharded_step(
            mesh, N, C, B, eta, pod_axis="pod" if multi else None
        )
        f_spec = jax.ShapeDtypeStruct((N,), jnp.float32, sharding=f_sh)
        ids_spec = jax.ShapeDtypeStruct((B,), jnp.int32)
        lowered = step.lower(f_spec, ids_spec)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        from repro.launch.hlo_analysis import analyze_collectives

        coll = analyze_collectives(compiled.as_text())
        mem = compiled.memory_analysis()
        result.update(
            {
                "ok": True,
                "flops": float(cost.get("flops", 0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0)),
                "collective_bytes_corrected": coll,
                "collective_bytes_corrected_total": float(sum(coll.values())),
                "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
                "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
                "compile_s": round(time.time() - t0, 2),
            }
        )
    except Exception as e:  # reprolint: allow(broad-except) recorded, not fatal to the sweep
        result.update({"ok": False, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-3000:]})
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"cache-dataplane__{mesh_kind}.json"), "w") as f:
        json.dump(result, f, indent=2)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--cache-cell", action="store_true",
                    help="dry-run the OGB cache data plane instead of an LM cell")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--subprocess", action="store_true",
                    help="run each cell in a fresh interpreter")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.cache_cell:
        for m in meshes:
            res = run_cache_cell(m, args.out)
            print(f"[cache ] {m}: ok={res.get('ok')} "
                  f"coll={res.get('collective_bytes_corrected_total')} "
                  f"err={res.get('error', '')[:160]}", flush=True)
        return

    if args.all:
        from repro.configs.base import cells

        todo = [(a, s, m) for (a, s) in cells() for m in meshes]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        todo = [(args.arch, args.shape, m) for m in meshes]

    for arch, shape, mesh_kind in todo:
        fname = os.path.join(args.out, f"{arch}__{shape}__{mesh_kind}.json")
        if not args.force and os.path.exists(fname):
            with open(fname) as f:
                prev = json.load(f)
            if prev.get("ok"):
                print(f"[cached] {arch} {shape} {mesh_kind}", flush=True)
                continue
        if args.subprocess:
            import subprocess

            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                "--out", args.out,
            ] + (["--force"] if args.force else [])
            print(f"[spawn ] {arch} {shape} {mesh_kind}", flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True)
            status = "ok" if r.returncode == 0 else f"rc={r.returncode}"
            print(f"[done  ] {arch} {shape} {mesh_kind}: {status}", flush=True)
            if r.returncode != 0:
                print(r.stderr[-2000:], flush=True)
        else:
            print(f"[run   ] {arch} {shape} {mesh_kind}", flush=True)
            res = run_cell(arch, shape, mesh_kind, args.out)
            ok = res.get("ok")
            extra = "" if ok else f" ERROR {res.get('error', '')[:200]}"
            print(
                f"[done  ] {arch} {shape} {mesh_kind}: ok={ok} "
                f"compile={res.get('compile_s')}s flops={res.get('flops')}{extra}",
                flush=True,
            )


if __name__ == "__main__":
    main()
