"""One run of one cell: find its files by name, set up, measure, check.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name
``BENCHMARK.json`` gives it:

* ``configs[].file``: the configuration (a JSON file of sizes, the
  reference's name and the limits of the comparison);
* ``bench/traffic/<traffic>.json``: the mix, read by ``bench.traffic``;
* ``bench/modes/<mode>.py``: the traffic mode the mix names, with
  ``KEYS``, the exact keys of its mix files, and ``Driver``, built as
  ``Driver(cfg, mix, seed, devices)`` over the cell's devices;
* ``bench/metrics/<metric>.py``: a reader, ``read(ctx) -> float | None``;
* ``bench/reference/<reference>.py``: the plain reference.

Adding a cell, a configuration, a mix, a mode, a metric or a reference
therefore adds files and entries, and edits none.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import re
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

#: what a mode's name may be: a file name under ``bench/modes/``
MODE_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")

#: longest traced window: a trace holds an event per device op, and a
#: window of dense replay runs some hundred thousand ops a second
TRACE_SECONDS = 3.0


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: {[e['name'] for e in entries]}")


def load_module(path: Path, name: str):
    """Import a reader, a reference or a mode from its file, whose name may
    hold dots (``api_host_ms_per_call.serve.py``)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not Path(path).is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_mode(mode: str, root: Path = ROOT):
    """The module of a traffic mode: ``bench/modes/<mode>.py``."""
    modes = Path(root) / "bench" / "modes"
    path = modes / f"{mode}.py"
    if not isinstance(mode, str) or not MODE_NAME.fullmatch(mode) or not path.is_file():
        found = sorted(str(p) for p in modes.glob("*.py"))
        raise ValueError(f"no traffic mode {mode!r}: looked for {path} among {found}")
    return load_module(path, f"bench.modes.{mode}")


def cell_files(manifest: dict, workload: str, root: Path = ROOT) -> tuple:
    """(cell, config, mix) of a workload, each read from its own file."""
    from bench import traffic

    cell = find(manifest["workloads"], workload, "workload")
    entry = find(manifest["configs"], cell["config"], "config")
    cfg = json.loads((Path(root) / entry["file"]).read_text())
    mix = traffic.load_mix(Path(root) / "bench" / "traffic" / f"{cell['traffic']}.json", root)
    return cell, cfg, mix


def metrics_of(manifest: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metric entries a cell reports."""
    return [m for m in manifest[kind] if workload in m.get("workloads", [workload])]


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            t_start: float, root: Path = ROOT, out=sys.stdout, err=sys.stderr) -> dict:
    """Run one cell once and return its result line (also printed).

    Does not look for a chip: ``bench/run.py`` does that first, and the
    tests call this on the CPU at tiny sizes."""
    import jax

    from bench import check, traffic
    from repro.analysis.recompile import track_compiles

    manifest = load_manifest(root)
    cell, cfg, mix = cell_files(manifest, workload, root)
    devices = jax.devices()[: int(cell["chips"])]
    driver = load_mode(mix["mode"], root).Driver(cfg, mix, seed, devices)
    driver.setup()
    window_s = min(seconds, TRACE_SECONDS) if trace else seconds
    setup_s = time.perf_counter() - t_start
    trace_dir = Path(root) / ".bench_out" / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with track_compiles() as compiles:
        stats = driver.window(window_s)
    if trace:
        jax.profiler.stop_trace()
    if "wake_late_p99_ms" in stats.extras:
        print(f"generator: woke late by p99 {stats.extras['wake_late_p99_ms']:.4f} ms, "
              f"max {stats.extras['wake_late_max_ms']:.4f} ms at "
              f"{stats.extras['offered_per_s']} decisions/s offered", file=err, flush=True)

    peak = memory_peak(devices)
    ids, got = driver.checked()
    driver.release()
    del driver
    gc.collect()
    ref_mod = load_module(Path(root) / "bench" / "reference" / f"{cfg['reference']}.py",
                          f"bench.reference.{cfg['reference']}")
    t_ref = time.perf_counter()
    want = ref_mod.replay(ids, cfg, traffic.seeds(seed)[1])
    print(f"reference: {len(ids)} windows in {time.perf_counter() - t_ref:.3f} s",
          file=err, flush=True)
    numbers = check.compare(got, want, cfg)
    numbers["window_compiles"] = float(compiles.executable_count + compiles.trace_count())
    verdict = check.judge(numbers, cfg["limits"])

    result = {
        "correct": verdict["correct"],
        "attempted": int(stats.attempted),
        "failed": int(stats.failed),
        "metrics": {},
        "device": {**device_info(devices), "memory_peak_bytes": peak},
    }
    ctx = {"stats": stats, "cfg": cfg, "mix": mix, "cell": cell, "setup_s": setup_s,
           "device_kind": devices[0].device_kind, "trace": None}
    if trace:
        from bench import trace_reduce

        red = trace_reduce.reduce_dir(trace_dir)
        ctx["trace"] = red
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["top_ops"], "idle_gaps": red["idle_by_span"]}
        kind = "per_layer"
    else:
        kind = "end_to_end"
    for m in metrics_of(manifest, workload, kind):
        reader = load_module(Path(root) / "bench" / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["checks"] = verdict["checks"]
    for name, c in verdict["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result
