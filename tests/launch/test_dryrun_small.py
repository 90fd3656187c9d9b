"""Dry-run machinery on a small fake mesh (8 host devices, subprocess)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")


def _run_small_dryrun(arch, shape):
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.configs.base import get_smoke, SHAPES, ShapeConfig
from repro.dist.param_sharding import param_shardings, batch_shardings, cache_shardings, state_shardings
from repro.dist.sharding import default_rules, use_sharding
from repro.models.model import forward_train, init_params, input_specs, decode_step
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import create_train_state, make_train_step
from repro.launch.hlo_analysis import analyze_collectives
from repro.launch.mesh import make_debug_mesh

cfg = get_smoke("{arch}")
shape = ShapeConfig("t", 32, 8, "{shape}")
mesh = make_debug_mesh(2, 4)
specs = input_specs(cfg, shape)
if shape.kind == "train":
    opt_cfg = OptimizerConfig(total_steps=10)
    step = make_train_step(cfg, opt_cfg)
    state_shape = jax.eval_shape(lambda: create_train_state(cfg, opt_cfg, jax.random.key(0)))
    s_sh = state_shardings(cfg, state_shape, mesh)
    b_sh = batch_shardings(mesh, specs)
    with use_sharding(mesh, default_rules()):
        compiled = jax.jit(step, in_shardings=(s_sh, b_sh)).lower(state_shape, specs).compile()
else:
    params_shape = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    p_sh = param_shardings(cfg, params_shape, mesh)
    c_sh = cache_shardings(cfg, specs["cache"], mesh)
    t_sh = batch_shardings(mesh, specs["tokens"])
    fn = lambda p, c, t: decode_step(cfg, p, c, t)
    with use_sharding(mesh, default_rules()):
        compiled = jax.jit(fn, in_shardings=(p_sh, c_sh, t_sh)).lower(
            params_shape, specs["cache"], specs["tokens"]).compile()
cost = compiled.cost_analysis()
coll = analyze_collectives(compiled.as_text())
print(json.dumps({{"flops": float(cost.get("flops", 0)), "collectives": coll}}))
"""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch", ["glm4-9b", "granite-moe-1b-a400m", "rwkv6-1.6b"])
def test_train_cell_compiles_small_mesh(arch):
    r = _run_small_dryrun(arch, "train")
    assert r["flops"] > 0
    # data parallelism must produce gradient reductions
    assert any("all-reduce" in k or "reduce-scatter" in k for k in r["collectives"]), r


@pytest.mark.parametrize("arch", ["mistral-nemo-12b", "jamba-1.5-large-398b"])
def test_decode_cell_compiles_small_mesh(arch):
    r = _run_small_dryrun(arch, "decode")
    assert r is not None


def test_production_mesh_shapes():
    """make_production_mesh axis layout (no device init needed for spec)."""
    import inspect

    from repro.launch import mesh as mesh_mod

    src = inspect.getsource(mesh_mod.make_production_mesh)
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '"pod", "data", "model"' in src


def test_dryrun_artifacts_complete():
    """Every runnable (arch x shape) cell has both mesh artifacts on disk."""
    from repro.configs.base import cells

    d = os.path.join(ROOT, "benchmarks", "results", "dryrun")
    if not os.path.isdir(d):
        pytest.skip("dry-run artifacts not generated yet")
    missing, failed = [], []
    for arch, shape in cells():
        for mesh_kind in ("single", "multi"):
            path = os.path.join(d, f"{arch}__{shape}__{mesh_kind}.json")
            if not os.path.exists(path):
                missing.append((arch, shape, mesh_kind))
                continue
            with open(path) as f:
                if not json.load(f).get("ok"):
                    failed.append((arch, shape, mesh_kind))
    assert not missing, f"missing dry-run cells: {missing}"
    assert not failed, f"failed dry-run cells: {failed}"
