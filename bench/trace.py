"""From a profiler trace to the numbers the per-layer metrics read.

A traced run writes one ``.xplane.pb``.  Its device planes
(``/device:TPU:<i>``) hold a line ``XLA Ops`` whose events are the
operations that ran on that chip, with start and duration on the host's
clock; the host plane holds the benchmark's own ``TraceAnnotation`` spans
(names starting ``bench.``).  Asynchronous copies (the ``Async XLA Ops``
line) span their whole wait and are not counted as busy.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


@dataclasses.dataclass
class Op:
    name: str        # the HLO text of the operation
    start: float     # ns
    end: float       # ns
    whole: bool = True   # False when the window's edge cut it

    @property
    def label(self) -> str:
        """The op's HLO name (``fusion.12``), marked when it is a Pallas
        kernel."""
        head = self.name.split(" = ", 1)[0].lstrip("%")
        return ("kernel " + head if 'custom_call_target="tpu_custom_call"'
                in self.name else head)


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Op]]             # device plane -> ops in the window
    spans: List[Tuple[str, float, float]]  # host annotations (name, s, e)
    window: Interval                     # ns, the bench.window span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {len(paths)}")
    return paths[0]


def load(path: str, window_span: str = "bench.window") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, List[Op]] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [Op(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith("bench.")]
    wins = [(s, e) for n, s, e in spans if n == window_span]
    if not wins:
        raise ValueError(f"no {window_span!r} span in the trace")
    window = (min(s for s, _ in wins), max(e for _, e in wins))
    clipped = {}
    for plane, evs in ops.items():
        clipped[plane] = [Op(o.name, max(o.start, window[0]),
                             min(o.end, window[1]),
                             window[0] <= o.start and o.end <= window[1])
                          for o in evs
                          if o.end > window[0] and o.start < window[1]]
    return Trace(clipped, spans, window)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def busy_ns(ops: List[Op]) -> float:
    return length(union([(o.start, o.end) for o in ops]))


def busy_s(tr: Trace) -> float:
    """Seconds in which an op ran, averaged over the device planes."""
    if not tr.ops:
        return 0.0
    return sum(busy_ns(v) for v in tr.ops.values()) / len(tr.ops) / 1e9


def idle_pct(tr: Trace) -> Optional[float]:
    """Share of the traced window in which no op ran, averaged over the
    chips; None when the trace holds no device."""
    if not tr.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s(tr) / tr.window_s)


def kernel_launches(tr: Trace):
    """``(operands, seconds)`` of every Pallas kernel launch wholly inside
    the window, over all chips."""
    from bench.work import kernel_operands

    out = []
    for ops in tr.ops.values():
        for o in ops:
            operands = kernel_operands(o.name) if o.whole else None
            if operands:
                out.append((operands, (o.end - o.start) / 1e9))
    return out


def _subtract(a: List[Interval], b: List[Interval]) -> float:
    """Length of ``union(a)`` not covered by ``union(b)``."""
    a, b = union(a), union(b)
    out, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out += e - cur
    return out


def is_collective(op: Op) -> bool:
    head = op.name.split("(", 1)[0]
    return any(c in head for c in COLLECTIVES)


def collective_exposed_pct(tr: Trace) -> Optional[float]:
    """Time in which a collective runs and no other op on that chip, over
    the traced window, averaged over chips; None without collectives."""
    if not tr.ops or not any(is_collective(o) for v in tr.ops.values()
                             for o in v):
        return None
    tot = 0.0
    for ops in tr.ops.values():
        coll = [(o.start, o.end) for o in ops if is_collective(o)]
        comp = [(o.start, o.end) for o in ops if not is_collective(o)
                and not o.name.split(" = ", 1)[0].lstrip("%").startswith(
                    "while")]
        tot += _subtract(coll, comp)
    return 100.0 * tot / len(tr.ops) / 1e9 / tr.window_s


def top_ops(tr: Trace, n: int = 10) -> List[list]:
    """The ops that took most device time (seconds, summed over chips),
    loops left out since their bodies are counted."""
    acc: Dict[str, float] = collections.Counter()
    for ops in tr.ops.values():
        for o in ops:
            if o.label.startswith("while"):
                continue
            acc[o.label] += (o.end - o.start) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> List[list]:
    """The longest stretches with no op on the first chip, each named by
    the innermost benchmark span open at its middle."""
    if not tr.ops:
        return []
    plane = sorted(tr.ops)[0]
    busy = union([(o.start, o.end) for o in tr.ops[plane]])
    edges = [tr.window[0]] + [x for iv in busy for x in iv] + [tr.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    inner = [(nm, s, e) for nm, s, e in tr.spans if nm != "bench.window"]

    def name(mid):
        return min(((e - s, nm) for nm, s, e in inner if s <= mid <= e),
                   default=(0, "bench.window"))[1]

    return [[name((s + e) / 2), (e - s) / 1e9] for s, e in gaps[:n]]
