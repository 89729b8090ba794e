#!/usr/bin/env python3
"""Host cost of the program's tracing while no profiler records.

    PYTHONPATH=src python scripts/span_cost.py [--n 200000]

Times, in microseconds, one enter and exit of ``obs.span`` at
``REPRO_OBS=off`` (with jax imported, as in every caller), one bare
``jax.profiler.TraceAnnotation`` for scale, and one start-and-stop of the
garbage-collection hook where the tree has one.  Each is the least of five
repeats.  Point ``PYTHONPATH`` at another tree's ``src`` to time that
tree's spans on the same machine.  Prints one JSON line.
"""

import argparse
import gc
import json
import timeit

import jax

from repro import obs
from repro.obs import trace


def per_call_us(fn, n: int) -> float:
    return min(timeit.repeat(fn, number=n, repeat=5)) / n * 1e6


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000)
    n = ap.parse_args().n
    obs.configure(level="off")

    def span():
        with obs.span("cost.span", tag=1):
            pass

    def annotation():
        with jax.profiler.TraceAnnotation("cost.annotation"):
            pass

    out = {"span_off_us": per_call_us(span, n),
           "annotation_us": per_call_us(annotation, n)}
    hook = getattr(trace, "_gc_span", None)
    if hook is not None:
        def collect():
            hook("start", {})
            hook("stop", {})

        out["gc_hook_off_us"] = per_call_us(collect, n)
        out["gc_hook_installed"] = hook in gc.callbacks
    print(json.dumps(out))


if __name__ == "__main__":
    main()
