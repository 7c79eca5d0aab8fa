"""Charge each idle instant of the device to the host span that held the
benchmark's unit thread at that instant.

Reads the ``.xplane.pb`` that ``devtrace`` reads. The unit thread is the
host line that holds the benchmark's ``bench_unit_<i>`` annotations. On it
the program writes a ``stage.<name>`` span around each of its stages
(``repro.core.profiling.stage``), JAX writes its compile spans, and jaxlib
a ``PjitFunction(<function>)`` span around each call of a jitted function.
Over the window of ``devtrace.reduce`` (first unit start to last unit end)
every instant in which the first device runs no program is charged to the
innermost stage or compile span open at that instant: to the stage's name,
to ``compile``, or to ``unstaged`` where neither is open (the sweep's own
glue and the benchmark's). The charges partition the window's idle time.

Compile spans: ``backend_compile`` and ``backend_compile_and_load``
(``jax/_src/compiler.py``), around an XLA compile. A load from the
persistent compile cache writes no span of its own and stays with the
span that encloses it.

The busy intervals come from the device clock, which sits about a
millisecond off the host's; ``devtrace`` does not clip busy time to the
window and this module does, so the charges add up to its idle share to
within that.

``bench/run.py`` does not call this module yet, so no metric of the result
line reads it: a traced run reduces the same file with ``reduce`` and
``shares`` once ``run.py`` does (``PERF.md`` §7).
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Tuple

from . import devtrace

STAGE_PREFIX = "stage."          # repro.core.profiling.SPAN_PREFIX
COMPILE_SPANS = ("backend_compile", "backend_compile_and_load")
CALL_PREFIX = "PjitFunction("
COMPILE = "compile"
UNSTAGED = "unstaged"
STAGES = ("trace_gen", "classify", "stack_distance", "cache_scan", "dram",
          "host_sync", "translate", "fault_wait")
LABELS = STAGES + (COMPILE, UNSTAGED)

Span = Tuple[float, float, str]


def _kept(name: str) -> bool:
    return (name.startswith((devtrace.UNIT_PREFIX, STAGE_PREFIX, CALL_PREFIX))
            or name in COMPILE_SPANS)


def read_xplane(path: str) -> dict:
    """``{"ops": {plane: [(start_ns, dur_ns, name)]}, "lines": [[(start_ns,
    end_ns, name)]]}``: the device programs as ``devtrace.read_xplane``
    gives them, and for each host thread its unit annotations and its
    stage, compile and jitted-call spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    lines: List[List[Span]] = []
    for plane in pd.planes:
        if devtrace._DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == devtrace.OPS_LINE:
                    ops[plane.name] = [(e.start_ns, e.duration_ns,
                                        devtrace._HASH.sub("", e.name)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([(e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in line.events if _kept(e.name)])
    return {"ops": ops, "lines": lines}


def unit_thread(lines: List[List[Span]]) -> Tuple[List[Span], List[Span]]:
    """``(units, spans)`` of the host line that holds the most unit
    annotations, each sorted by start."""
    def units_of(line):
        return sorted(sp for sp in line if sp[2].startswith(devtrace.UNIT_PREFIX))
    line = max(lines, key=lambda ln: len(units_of(ln)), default=[])
    return units_of(line), sorted(sp for sp in line
                                  if not sp[2].startswith(devtrace.UNIT_PREFIX))


def _label(name: str) -> str:
    return COMPILE if name in COMPILE_SPANS else name[len(STAGE_PREFIX):]


def _innermost(spans: List[Span], lo: float, hi: float) -> List[Span]:
    """``[(start, end, label)]`` covering ``[lo, hi)`` in order: at each
    instant the label of the latest-started span open then, or
    ``unstaged`` where none is."""
    order = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    bounds = sorted({lo, hi} | {x for s, e, _ in spans for x in (s, e) if lo < x < hi})
    out, open_, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(order) and order[i][0] <= a:
            open_.append(order[i])
            i += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        out.append((a, b, open_[-1][2] if open_ else UNSTAGED))
    return out


def _idle(busy: List[List[float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The parts of ``[lo, hi)`` that the merged ``busy`` intervals leave."""
    idle, t = [], lo
    for s, e in busy:
        if e <= lo or s >= hi:
            continue
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if t < hi:
        idle.append((t, hi))
    return idle


def _enclosing(spans: List[Span], s: float, e: float, pick) -> str:
    """Name of the latest-started span among ``pick`` that holds ``[s, e]``."""
    inside = [sp for sp in spans if pick(sp[2]) and sp[0] <= s and e <= sp[1]]
    return inside[-1][2] if inside else ""


def _compiles(spans: List[Span], lo: float, hi: float) -> dict:
    """The compile spans that start in the window, counted with their
    seconds by jitted function and by enclosing stage."""
    by_function: Dict[str, list] = defaultdict(lambda: [0, 0.0])
    by_stage: Dict[str, list] = defaultdict(lambda: [0, 0.0])
    count, seconds = 0, 0.0
    for s, e, name in spans:
        if name not in COMPILE_SPANS or not lo <= s < hi:
            continue
        call = _enclosing(spans, s, e, lambda n: n.startswith(CALL_PREFIX))
        function = call[len(CALL_PREFIX):-1] if call else "?"
        stage = _enclosing(spans, s, e, lambda n: n.startswith(STAGE_PREFIX))
        stage = stage[len(STAGE_PREFIX):] if stage else UNSTAGED
        for table, key in ((by_function, function), (by_stage, stage)):
            table[key][0] += 1
            table[key][1] += (e - s) / 1e9
        count += 1
        seconds += (e - s) / 1e9
    return {"count": count, "seconds": seconds,
            "by_function": dict(by_function), "by_stage": dict(by_stage)}


def reduce(trace: dict) -> dict:
    """``window_s``, the idle seconds charged to each of ``LABELS``
    (``idle_s``) and the window's compiles; ``{}`` where the trace holds
    no device program, no unit or no stage span."""
    ops = trace["ops"]
    units, spans = unit_thread(trace["lines"])
    if not units or not ops or not any(n.startswith(STAGE_PREFIX) for _, _, n in spans):
        return {}
    lo, hi = units[0][0], max(u[1] for u in units)
    busy = devtrace._union([(s, s + d) for s, d, _ in ops[sorted(ops)[0]]])
    charged = [(s, e, _label(n)) for s, e, n in spans
               if n.startswith(STAGE_PREFIX) or n in COMPILE_SPANS]
    segments = _innermost(charged, lo, hi)
    idle_ns = dict.fromkeys(LABELS, 0.0)
    j = 0
    for s, e in _idle(busy, lo, hi):
        while segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b, label = segments[k]
            idle_ns[label] = idle_ns.get(label, 0.0) + min(b, e) - max(a, s)
            k += 1
    return {"window_s": (hi - lo) / 1e9,
            "idle_s": {k: v / 1e9 for k, v in idle_ns.items()},
            "compiles": _compiles(spans, lo, hi)}


def shares(reduced: dict) -> Dict[str, float]:
    """Per cent of the window in which the device was idle while each of
    ``LABELS`` held the unit thread; ``{}`` where ``reduce`` had nothing to
    charge."""
    if not reduced or reduced["window_s"] <= 0:
        return {}
    return {k: 100.0 * v / reduced["window_s"] for k, v in reduced["idle_s"].items()}
