"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

What a TPU trace holds (read by hand from a v5e trace of the round
program): one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops``
has one event per executed HLO instruction, named by its HLO text
(``%fusion.728 = bf16[...] fusion(...)``). A ``while`` (a ``lax.scan``)
is an event of its own that contains its body's events, so events nest.
The line ``XLA Modules`` has one event per program run, named
``jit_<function>(<fingerprint>)``. Host planes (``/host:CPU``) have a line
per thread; the benchmark's ``jax.profiler.TraceAnnotation`` spans sit on
the main thread's line. All times are nanoseconds on one clock.

The reduction reports, per chip and then averaged over chips:

- busy time: the union of the op intervals;
- device time per program, by module name without its fingerprint;
- collective time that no compute overlaps: the union of the innermost
  collective ops' intervals and of the collectives in flight on the line
  ``Async XLA Ops``, less the union of the innermost other ops';
- the ops with the most self time (time not covered by a nested op);
- idle time inside the window, by what the host was doing: the
  benchmark's annotations around each gap, and the program's own spans
  where the caller adds them to ``Trace.host``.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all", "collective-broadcast")
ANNOTATION_PREFIX = "bench."
WINDOW = "bench.window"  # the measured window's annotation


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` less ``b``, both unions."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def op_name(hlo_text: str) -> str:
    """``%fusion.728 = bf16[...] fusion(...)`` -> ``fusion.728``."""
    return hlo_text.split(" = ")[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_round_fn(8915476382633809733)`` -> ``jit_round_fn``."""
    return event_name.split("(")[0]


class Trace:
    """The events the reduction needs, taken out of the profile."""

    def __init__(self):
        self.ops: Dict[str, List[Tuple[float, float, str]]] = {}  # chip -> (s, e, hlo text)
        self.modules: Dict[str, List[Tuple[float, float, str]]] = {}
        self.async_ops: Dict[str, List[Tuple[float, float, str]]] = {}  # copies, collectives in flight
        self.host: List[Tuple[float, float, str]] = []  # annotations and program frames

    @classmethod
    def load(cls, path: str) -> "Trace":
        import jax

        return cls.from_profile(jax.profiler.ProfileData.from_file(path))

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        t = cls()
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        t.ops[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                                             for e in line.events]
                    elif line.name == "Async XLA Ops":
                        t.async_ops[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                                                   for e in line.events]
                    elif line.name == "XLA Modules":
                        t.modules[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                                                 for e in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(ANNOTATION_PREFIX):
                            t.host.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
        return t

    def annotation(self, name: str) -> Optional[Interval]:
        spans = [(s, e) for s, e, n in self.host if n == name]
        if not spans:
            return None
        return min(s for s, _ in spans), max(e for _, e in spans)


def _self_times(events: List[Tuple[float, float, str]]) -> Tuple[Dict[str, float], List[int]]:
    """Self time per op name, and the indices of innermost events (those
    that contain no other event), for properly nested events."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    self_t: Dict[str, float] = defaultdict(float)
    has_child = [False] * len(events)
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            has_child[stack[-1]] = True
            parent = stack[-1]
            self_t[op_name(events[parent][2])] -= min(e, events[parent][1]) - s
        self_t[op_name(events[i][2])] += e - s
        stack.append(i)
    leaves = [i for i in range(len(events)) if not has_child[i]]
    return self_t, leaves


def _labels(host: List[Tuple[float, float, str]], times: List[float]) -> List[str]:
    """For each time (ascending), the annotations around it inside the
    window's, outermost first: ``bench.init > bench.sensitivity``."""
    events = sorted(ev for ev in host if ev[2] != WINDOW)
    out, active, i = [], [], 0
    for t in times:
        while i < len(events) and events[i][0] <= t:
            active.append(events[i])
            i += 1
        active = [ev for ev in active if ev[1] > t]
        names = [n for _, _, n in sorted(active)]
        out.append(" > ".join(names) if names else "between steps")
    return out


def reduce(trace: Trace, window: Optional[Interval] = None, top: int = 10) -> Dict:
    """Device metrics of ``trace`` inside ``window`` (default: the span of
    all device events)."""
    chips = sorted(trace.ops)
    if not chips:
        return {"chips": 0}
    if window is None:
        lo = min(s for c in chips for s, _, _ in trace.ops[c])
        hi = max(e for c in chips for _, e, _ in trace.ops[c])
        window = (lo, hi)
    lo, hi = window
    busy, exposed = [], []
    modules: Dict[str, float] = defaultdict(float)
    module_runs: Dict[str, int] = defaultdict(int)
    self_total: Dict[str, float] = defaultdict(float)
    idle: List[Interval] = []
    for c in chips:
        evs = [(s, e, n) for s, e, n in trace.ops[c] if e > lo and s < hi]
        b = union([(max(s, lo), min(e, hi)) for s, e, _ in evs])
        busy.append(measure(b))
        st, leaves = _self_times(evs)
        for k, v in st.items():
            self_total[k] += v / len(chips)
        is_coll = [any(x in op_name(n) for x in COLLECTIVES) for _, _, n in evs]
        coll = union([(evs[i][0], evs[i][1]) for i in leaves if is_coll[i]]
                     + [(s, e) for s, e, n in trace.async_ops.get(c, [])
                        if any(x in op_name(n) for x in COLLECTIVES)])
        comp = union([(evs[i][0], evs[i][1]) for i in leaves if not is_coll[i]])
        exposed.append(measure(clip(subtract(coll, comp), lo, hi)))
        for s, e, n in trace.modules.get(c, []):
            if e > lo and s < hi:
                modules[module_name(n)] += (min(e, hi) - max(s, lo)) / len(chips)
                module_runs[module_name(n)] += 1
        if c == chips[0]:
            idle = subtract([(lo, hi)], b)
    gaps: Dict[str, float] = defaultdict(float)
    for (s, e), label in zip(idle, _labels(trace.host, [(s + e) / 2 for s, e in idle])):
        gaps[label] += (e - s) * 1e-9
    ns = 1e-9
    return {
        "chips": len(chips),
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / len(chips) * ns,
        "exposed_collective_s": max(exposed) * ns,
        "modules_s": {k: v * ns for k, v in modules.items()},
        "module_runs": {k: v // len(chips) for k, v in module_runs.items()},
        "device_ops": sorted(([k, v * ns] for k, v in self_total.items()), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1])[:top],
    }
