"""From a profiler trace to the numbers the per-layer metrics read.

``load_trace`` reads the ``.xplane.pb`` that ``jax.profiler`` writes,
with ``jax.profiler.ProfileData``, into a :class:`Trace`: per device the
XLA op events and the XLA module (program) events, and the harness's
host spans (``bench.*``), all on one clock in nanoseconds.  The
functions below reduce it: busy time as the union of op intervals, idle
gaps named by the host span they fall in, time per program and per op,
and collective time during which no other op runs.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")


@dataclasses.dataclass
class Ev:
    name: str
    start: float         # ns
    end: float           # ns
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: dict            # device index -> [Ev] XLA ops, sorted by start
    modules: dict        # device index -> [Ev] XLA modules (programs)
    spans: list          # host spans named bench.*, sorted by start

    @property
    def window(self) -> tuple:
        """(start, end) ns of the traced window: the harness's
        ``bench.trace_window`` span, else the extent of the device ops."""
        for s in self.spans:
            if s.name == "bench.trace_window":
                return s.start, s.end
        evs = [e for d in self.ops.values() for e in d]
        return min(e.start for e in evs), max(e.end for e in evs)

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def spans_named(self, prefix: str) -> list:
        lo, hi = self.window
        return [s for s in self.spans if s.name.startswith(prefix)
                and s.start >= lo and s.end <= hi]


def _events(line, keep_stats: bool):
    out = []
    for e in line.events:
        stats = dict(e.stats) if keep_stats else {}
        out.append(Ev(e.name, float(e.start_ns),
                      float(e.start_ns + e.duration_ns), stats))
    out.sort(key=lambda e: e.start)
    return out


def from_profile(pd) -> Trace:
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = _events(line, False)
                elif line.name == "XLA Modules":
                    modules[dev] = _events(line, False)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line, True)
                             if e.name.startswith("bench."))
    spans.sort(key=lambda e: e.start)
    return Trace(ops, modules, spans)


def load_trace(directory) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(Path(directory).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return from_profile(ProfileData.from_file(str(paths[-1])))


# -- interval arithmetic ------------------------------------------------------
def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
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


# -- reductions ---------------------------------------------------------------
def busy(trace: Trace, dev: int, lo=None, hi=None) -> list:
    """Disjoint intervals in which an op ran on device ``dev``, within
    [lo, hi] (default: the traced window)."""
    wlo, whi = trace.window
    lo = wlo if lo is None else lo
    hi = whi if hi is None else hi
    return clip(union((e.start, e.end) for e in trace.ops.get(dev, ())),
                lo, hi)


def busy_s(trace: Trace) -> float:
    """Busy seconds in the window, averaged over the traced devices."""
    devs = sorted(trace.ops)
    return sum(length(busy(trace, d)) for d in devs) / len(devs) * 1e-9


def idle_gaps(trace: Trace, dev: int = 0) -> list:
    """(name, seconds) of the device's idle time in the window, summed by
    the innermost host span the host was in ("no span" where it was in
    none), longest first.  A gap is cut where a span starts or ends."""
    lo, hi = trace.window
    gaps = subtract([(lo, hi)], busy(trace, dev))
    spans = [s for s in trace.spans if s.name != "bench.trace_window"]
    total = {}
    for s, e in gaps:
        near = [sp for sp in spans if sp.end > s and sp.start < e]
        cuts = sorted({s, e} | {x for sp in near for x in (sp.start, sp.end)
                                if s < x < e})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inner = [sp for sp in near if sp.start <= mid <= sp.end]
            name = (min(inner, key=lambda sp: sp.dur).name if inner
                    else "no span")
            total[name] = total.get(name, 0.0) + (b - a) * 1e-9
    return sorted(total.items(), key=lambda kv: -kv[1])


HLO_OP = re.compile(r"^%?([\w.-]+) = (\(.*?\)|\S+) ([\w-]+)\(")
CONTAINERS = ("while", "conditional", "call")


def op_label(name: str) -> str:
    """A short label for a TPU op event, whose name is the HLO
    instruction's text: ``fusion.322 bf16[8192,16,2,128] fusion``, with
    ``custom_call_target`` where there is one."""
    m = HLO_OP.match(name)
    if not m:
        return name[:120]
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))[:60]
    label = f"{m.group(1)} {shape} {m.group(3)}"
    t = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{label} {t.group(1)}" if t else label


def op_seconds(trace: Trace, dev: int = 0) -> list:
    """(op label, seconds) on the device in the window, most first; ops
    that only contain others (a scan's ``while``) are left out."""
    lo, hi = trace.window
    total = {}
    for e in trace.ops.get(dev, ()):
        d = min(e.end, hi) - max(e.start, lo)
        m = HLO_OP.match(e.name)
        if d > 0 and not (m and m.group(3) in CONTAINERS):
            k = op_label(e.name)
            total[k] = total.get(k, 0.0) + d * 1e-9
    return sorted(total.items(), key=lambda kv: -kv[1])


def module_events(trace: Trace, pattern: str, dev: int = 0) -> list:
    """Program events on ``dev`` whose name contains ``pattern``, wholly
    inside the window."""
    lo, hi = trace.window
    return [e for e in trace.modules.get(dev, ())
            if pattern in e.name and e.start >= lo and e.end <= hi]


def ops_in(trace: Trace, pattern, dev: int = 0) -> list:
    """(op, enclosing program event or None) for ops on ``dev`` in the
    window whose name matches the regular expression ``pattern``."""
    lo, hi = trace.window
    mods = trace.modules.get(dev, [])
    out, j = [], 0
    for e in trace.ops.get(dev, ()):
        if not (e.start >= lo and e.end <= hi and re.search(pattern, e.name)):
            continue
        while j < len(mods) and mods[j].end < e.start:
            j += 1
        m = mods[j] if j < len(mods) and mods[j].start <= e.start else None
        out.append((e, m))
    return out


def exposed_collective_s(trace: Trace, dev: int = 0) -> float:
    """Seconds in the window in which a collective op ran on ``dev`` and
    no other op did."""
    lo, hi = trace.window
    coll, comp = [], []
    for e in trace.ops.get(dev, ()):
        (coll if COLLECTIVE.search(e.name) else comp).append(
            (e.start, e.end))
    return length(subtract(clip(union(coll), lo, hi),
                           clip(union(comp), lo, hi))) * 1e-9
