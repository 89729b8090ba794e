"""The program's own spans in a profiler trace, and what they explain.

The program annotates its layers (``repro.obs.span``, a
``jax.profiler.TraceAnnotation`` under a bare name): ``update_model`` with
its children ``update_model.ingest``, ``.dispatch`` and ``.wait``; the
serving worker's ``serve.worker.flush`` over the engine's ``serve.flush``
and ``serve.bucket``; ``serve.plan.build`` on a plan-cache miss; ``jt.*``
in the junction-tree engine; and ``gc`` for each garbage collection.  They
sit on the trace's host plane, on the clock of the device ops, beside the
harness's ``bench.*`` spans.

``load`` reads a trace as ``bench.trace.load`` does and keeps these spans
too, so ``bench.trace.idle_gaps`` names each idle gap by the innermost of
them open at its middle.  ``idle_under`` splits the chip's idle time by the
innermost program span open at each moment; ``estep_pct`` is the share of
the chip's peak that the E-step passes the program counted would need.
"""

from __future__ import annotations

import collections
import heapq
from typing import Dict, List, Tuple

from bench import trace, work

PREFIXES = ("update_model", "serve.", "jt.")


def is_program(name: str) -> bool:
    return name == "gc" or name.startswith(PREFIXES)


def program_spans(path: str) -> List[Tuple[str, float, float]]:
    """``(name, start_ns, end_ns)`` of every program span in the trace, on
    any host thread."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events
                        if is_program(e.name)]
    return out


def load(path: str) -> trace.Trace:
    """``bench.trace.load`` with the program's spans kept beside the
    harness's."""
    tr = trace.load(path)
    tr.spans = sorted(tr.spans + program_spans(path), key=lambda s: s[1])
    return tr


def innermost_segments(tr: trace.Trace) -> List[Tuple[str, float, float]]:
    """The window cut where a program span opens or closes, each piece
    named by the innermost (shortest) program span open over it, on any
    thread; pieces under no program span are left out."""
    w0, w1 = tr.window
    spans = [(n, max(s, w0), min(e, w1)) for n, s, e in tr.spans
             if is_program(n) and e > w0 and s < w1]
    edges = sorted({x for _, s, e in spans for x in (s, e)})
    starts = sorted(range(len(spans)), key=lambda i: spans[i][1])
    open_: list = []              # (duration, index), lazily pruned
    out = []
    k = 0
    for a, b in zip(edges, edges[1:]):
        while k < len(starts) and spans[starts[k]][1] <= a:
            i = starts[k]
            heapq.heappush(open_, (spans[i][2] - spans[i][1], i))
            k += 1
        # the shortest span left is open over [a, b): every edge cuts
        while open_ and spans[open_[0][1]][2] <= a:
            heapq.heappop(open_)
        if open_:
            out.append((spans[open_[0][1]][0], a, b))
    return out


def idle_by_span(tr: trace.Trace) -> Dict[str, float]:
    """Idle nanoseconds of the chip under each innermost program span,
    summed over chips."""
    segs = innermost_segments(tr)
    acc: Dict[str, float] = collections.Counter()
    for ops in tr.ops.values():
        busy = trace.union([(o.start, o.end) for o in ops])
        j = 0
        for name, a, b in segs:
            while j < len(busy) and busy[j][1] <= a:
                j += 1
            covered, m = 0.0, j
            while m < len(busy) and busy[m][0] < b:
                covered += min(b, busy[m][1]) - max(a, busy[m][0])
                m += 1
            acc[name] += (b - a) - covered
    return dict(acc)


def idle_under(tr: trace.Trace, name: str) -> float:
    """Share (%) of the traced window in which the chip is idle and the
    innermost open program span is ``name``, averaged over chips; 0 when
    the span never opens, None when the trace holds no device."""
    if not tr.ops or tr.window_s <= 0:
        return None
    idle = idle_by_span(tr).get(name, 0.0)
    return 100.0 * idle / len(tr.ops) / 1e9 / tr.window_s


def estep_pct(cfg: dict, passes, batch: int, window_s: float, chips: int,
              peaks) -> float:
    """The least time of ``passes`` E-step passes, each over one batch of
    ``batch`` instances (``bench.work.estep_pass``), over the window times
    the chips: ``learn_mfu`` from the program's counters instead of the
    kernel launches.  ``last_update.passes`` counts the stream path's
    scoring pass too, which reads every instance but launches no Gram
    kernel (nothing uses its statistics), so ``learn_mfu`` leaves it out;
    ``last_update.sweeps`` counts what the launches count."""
    one = work.least_seconds(*work.estep_pass(cfg, batch), peaks)
    return 100.0 * one * sum(passes) / (window_s * chips)
