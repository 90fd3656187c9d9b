"""What the program's own spans and named scopes say in a profiler trace.

``bench.trace_reduce`` reduces a trace to the benchmark's ``bench.*``
spans and the device operations.  This module reads, over the same window
(the first to the last ``bench.*`` span) and the same device busy time:

* ``program_spans``: for each ``repro.*`` span name (the program's
  ``jax.profiler.TraceAnnotation`` spans), how many, their seconds, their
  self seconds (less the part their child spans on the same thread
  cover), and the device busy time inside them;
* ``idle_by_program_span``: the window's idle device time put down to the
  innermost ``repro.*`` span covering it (across threads, the one begun
  last), or ``(no program span)`` where none does;
* ``scopes``: device time per named-scope path (``ogb_tree/solve``,
  ``ogb/project``), averaged over the devices.  Only leaf operations
  count: an operation that encloses others on its line (a ``while``,
  ``conditional`` or ``call``) is their sum, not more time.

The device operations of a trace carry no scope.  The HLO text of the
executables that ran does: each instruction's ``op_name`` metadata holds
the ``jax.named_scope`` path it was traced under.  An instruction without
one (a few wrapped reductions and copies) takes the one scope of its
operands, else the scope of the instruction that runs its computation (a
``while``'s body takes the ``while``'s).  The key is the path from its
first ``ogb_tree`` or ``ogb`` segment on, cut to two levels, so
``jit(run_fn)/while/body/ogb_tree/solve/while/body/mul`` counts under
``ogb_tree/solve``.

A traced run of the benchmark leaves this reduction beside its trace, as
``program_trace.json``, for the reader who wants more than the metrics.
"""

from __future__ import annotations

import heapq
import json
import re
from pathlib import Path

from bench.trace_reduce import OPS_LINE, SPAN_PREFIX, _Busy, _merge

PROGRAM_PREFIX = "repro."
NO_PROGRAM_SPAN = "(no program span)"
NO_SCOPE = "(no scope)"
SCOPE_ROOTS = ("ogb_tree", "ogb")

_INSTR = re.compile(r"\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([^\s,})]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([^\s,(){}]+)")
_OP_KEY = re.compile(r"%?([^\s=]+)")


def _op_key(event_name: str) -> str:
    """``fusion.124`` from a CPU event's name or a TPU event's
    ``%fusion.124 = f32[...] fusion(...)``."""
    m = _OP_KEY.match(event_name)
    return m.group(1) if m else event_name


def scope_of(op_name: str):
    """``ogb_tree/solve`` from ``.../ogb_tree/solve/while/body/mul``."""
    segs = op_name.split("/")
    for i, seg in enumerate(segs[:-1]):
        if seg in SCOPE_ROOTS:
            return f"{seg}/{segs[i + 1]}"
    return None


def hlo_scopes(text: str) -> dict:
    """Instruction name -> scope (or None) over one executable's HLO text."""
    own, operands, caller, comp_of = {}, {}, {}, {}
    comp = None
    for line in text.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = _op_key(line.removeprefix("ENTRY "))
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name, rest = m.group(1), line[m.end():]
        op = _OP_NAME.search(rest)
        own[name] = op.group(1) if op else None
        operands[name] = _OPERAND.findall(rest)
        comp_of[name] = comp
        callees = _CALLEE.findall(rest)
        for group in _BRANCHES.findall(line):
            callees += [c.strip().lstrip("%") for c in group.split(",")]
        for c in callees:
            caller.setdefault(c, name)

    scopes: dict = {}

    def resolve(name):
        if name in scopes:
            return scopes[name]
        scopes[name] = None  # while resolving: no cycle can recurse forever
        if own[name] is not None:
            scope = scope_of(own[name])
        else:
            found = {resolve(o) for o in operands[name] if o in own} - {None}
            up = caller.get(comp_of[name])
            scope = found.pop() if len(found) == 1 else resolve(up) if up in own else None
        scopes[name] = scope
        return scope

    for name in own:  # in text order: operands before their users
        resolve(name)
    return scopes


def _pick(scope_maps: list, seen: set) -> dict:
    """One op-name -> scope map for the ops a trace holds.

    Executables of one function share instruction names, so the maps that
    cover most of the trace's op names are taken and merged; a name they
    place in different scopes gets none."""
    if not scope_maps:
        return {}
    best = max(len(seen.intersection(m)) for m in scope_maps)
    merged: dict = {}
    for m in scope_maps:
        if len(seen.intersection(m)) != best:
            continue
        for name, scope in m.items():
            merged[name] = scope if merged.get(name, scope) == scope else None
    return merged


def _op_lines(planes: list) -> list:
    """Per device, its operation lines, each a list of (name, start, end,
    whether the event is an HLO operation)."""
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns, True)
                      for e in line.events]
                     for line in plane.lines if line.name == OPS_LINE]
            if any(lines):
                devices.append(lines)
    if not devices:
        # the CPU backend runs its operations on host threads, beside the
        # runtime's own events; busy time counts both, as in
        # bench.trace_reduce, and scopes only the events of an HLO op
        devices = [[[(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      any(k == "hlo_op" for k, _ in e.stats))
                     for e in line.events if not e.name.startswith(("end: ", "Thread"))]
                    for plane in planes if plane.name == "/host:CPU"
                    for line in plane.lines if line.name.startswith("tf_XLA")]]
    return devices


def _nesting(items: list) -> tuple:
    """``items`` of one line, (name, start, end), sorted by start, and the
    index of the innermost item enclosing each (None at the top)."""
    items = sorted(items, key=lambda it: (it[1], -it[2]))
    parent: list = []
    stack: list = []
    for i, (_, s, e) in enumerate(items):
        while stack and items[stack[-1]][2] <= s:
            stack.pop()
        parent.append(stack[-1] if stack and e <= items[stack[-1]][2] else None)
        stack.append(i)
    return items, parent


def _leaves(ops: list) -> list:
    """The operations of one line that enclose no other operation."""
    ops, parent = _nesting(ops)
    enclosing = set(parent)
    return [o for i, o in enumerate(ops) if i not in enclosing]


def _span_times(spans: list) -> list:
    """(name, start, end, seconds of its child spans) of one thread's spans."""
    spans, parent = _nesting(spans)
    child = [0.0] * len(spans)
    for (_, s, e), p in zip(spans, parent):
        if p is not None:
            child[p] += e - s
    return [(n, s, e, c) for (n, s, e), c in zip(spans, child)]


def _idle_by_owner(spans: list, busies: list, w0: float, w1: float) -> dict:
    """Idle time in [w0, w1) by the latest-begun span covering it."""
    k = len(busies)
    edges = sorted({w0, w1, *(s for _, s, _ in spans), *(e for _, _, e in spans)})
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    heap: list = []
    nxt = 0
    idle: dict = {}
    for a, b in zip(edges, edges[1:]):
        while nxt < len(order) and spans[order[nxt]][1] <= a:
            i = order[nxt]
            # begun last first; of two begun together, the inner (ends first)
            heapq.heappush(heap, (-spans[i][1], spans[i][2], i))
            nxt += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        owner = spans[heap[0][2]][0] if heap else NO_PROGRAM_SPAN
        gap = (b - a) - sum(bz.within(a, b) for bz in busies) / k
        idle[owner] = idle.get(owner, 0.0) + gap
    return idle


def reduce_planes(planes, scope_maps=()) -> dict:
    """The reduction over planes that look like ``ProfileData.planes``;
    ``scope_maps`` are :func:`hlo_scopes` of the executables that may
    have run."""
    planes = list(planes)
    bench, program = [], []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                   if e.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX))]
            bench += [ev for ev in evs if ev[0].startswith(SPAN_PREFIX)]
            program.append([ev for ev in evs if ev[0].startswith(PROGRAM_PREFIX)])
    if not bench:
        raise ValueError("the trace holds no host span named bench.*")
    w0 = min(s for _, s, _ in bench)
    w1 = max(e for _, _, e in bench)
    devices = _op_lines(planes)

    def clip(evs):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in evs if e > w0 and s < w1]

    busies = [_Busy(_merge([(s, e) for line in lines for _, s, e in clip(ev[:3] for ev in line)]))
              for lines in devices]
    k = len(busies)

    per_span: dict = {}
    clipped_spans = []
    for line in program:
        for name, s, e, child in _span_times(clip(line)):
            d = per_span.setdefault(name, {"count": 0, "seconds": 0.0, "self_s": 0.0,
                                           "busy_s": 0.0})
            d["count"] += 1
            d["seconds"] += (e - s) * 1e-9
            d["self_s"] += (e - s - child) * 1e-9
            d["busy_s"] += sum(b.within(s, e) for b in busies) / k * 1e-9
            clipped_spans.append((name, s, e))
    idle = _idle_by_owner(clipped_spans, busies, w0, w1)

    leaves = [clip(_leaves([ev[:3] for ev in line if ev[3]]))
              for lines in devices for line in lines]
    seen = {_op_key(n) for ops in leaves for n, _, _ in ops}
    scope_map = _pick(list(scope_maps), seen)
    scopes: dict = {}
    for ops in leaves:
        for n, s, e in ops:
            scope = scope_map.get(_op_key(n)) or NO_SCOPE
            scopes[scope] = scopes.get(scope, 0.0) + (e - s) * 1e-9 / k
    return {
        "program_spans": per_span,
        "idle_by_program_span": sorted(([n, v * 1e-9] for n, v in idle.items() if v > 0),
                                       key=lambda kv: -kv[1]),
        "scopes": dict(sorted(scopes.items(), key=lambda kv: -kv[1])),
    }


def reduce_file(path, hlo_texts=()) -> dict:
    import jax

    return reduce_planes(jax.profiler.ProfileData.from_file(str(path)).planes,
                         [hlo_scopes(t) for t in hlo_texts])


def program_hlo() -> list:
    """The HLO text of the program's compiled executables in this process;
    empty where the program has no way to give it."""
    from repro.cachesim import api

    texts = getattr(api, "cached_executable_texts", None)
    return texts() if texts is not None else []


def for_ctx(ctx: dict, root) -> dict | None:
    """The reduction of a traced run's trace, kept in the run's ``ctx`` for
    all its readers; None where the run was not traced.  ``root`` is the
    checkout whose ``.bench_out/trace/<cell>`` the harness traced into."""
    if ctx.get("trace") is None:
        return None
    if "program_trace" not in ctx:
        trace_dir = Path(root) / ".bench_out" / "trace" / ctx["cell"]["name"]
        found = sorted(trace_dir.rglob("*.xplane.pb"))
        red = reduce_file(found[0], program_hlo()) if len(found) == 1 else None
        if red is not None:
            (trace_dir / "program_trace.json").write_text(json.dumps(red, indent=1))
        ctx["program_trace"] = red
    return ctx["program_trace"]
