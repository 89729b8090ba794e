#!/usr/bin/env python3
"""Readings that the output check's limits are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,13 [--seconds 2]

For every seed, in one process: the program's readings (a run of the cell
with its window shortened to ``--seconds``), the control's (the reference
computed in bfloat16, one step below the float32 that the configuration
states, put in the program's place) and the readings of each fault that the
cell can have and that needs a run:

- learning cells: ``half_batch``, the reference fed half of every batch
  (a step that returns its state unchanged reads 1 on both updates by
  construction and needs no run);
- serving cells: ``altered``, the reference's answers with the first two
  classes swapped where they are produced.

Prints one JSON line per seed and a summary: the largest sound reading and
the smallest control and fault readings of each number.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def learn_extra(cell):
    import jax.numpy as jnp

    from bench import compare
    from bench.drivers import learn_closed as lc

    feed = lc.Feed(cell.cfg, cell.traffic, cell.seed)
    ref = cell.reference()
    base = lc.reference_steps(cell, feed, ref, jnp.float32)
    out = {}
    for name, kw in (("control", dict(dtype=jnp.bfloat16)),
                     ("half_batch", dict(dtype=jnp.float32,
                                         fault="half_batch"))):
        rec = lc.reference_steps(cell, feed, ref, **kw)
        out[name] = compare.learn_readings(rec, base)
    return out


def serve_extra(cell):
    import jax.numpy as jnp
    import numpy as np

    from bench import compare, gen
    from bench.drivers import serve_open as so

    t = cell.traffic
    xc, xd = gen.host_batches(cell.cfg, t, cell.seed, 1, t["pool"])
    _, rows = so.schedule(cell.seed, t["rate_qps"], cell.seconds, xc.shape[1])
    ref = cell.reference()
    base = np.asarray(so.reference_answers(cell, ref, jnp.float32, xc[0],
                                           xd[0], rows), np.float64)
    ctrl = np.asarray(so.reference_answers(cell, ref, jnp.bfloat16, xc[0],
                                           xd[0], rows), np.float64)
    altered = base.copy()
    altered[:, [0, 1]] = altered[:, [1, 0]]
    return {"control": compare.serve_readings(ctrl, base),
            "altered": compare.serve_readings(altered, base)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = run.run_cell(args.workload, seed, args.seconds, False)
        cell = run.load_cell(args.workload, seed, args.seconds, False)
        import jax

        cell.devices = jax.devices()[:cell.chips]
        extra = (serve_extra if cell.traffic["kind"] == "serve_open"
                 else learn_extra)(cell)
        row = {"seed": seed, "correct": res["correct"],
               "program": {k: v["value"] for k, v in res["checks"].items()},
               **extra, "metrics": {k: v["value"]
                                    for k, v in res["metrics"].items()}}
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"largest_sound": {}, "smallest": {}}
    for k in rows[0]["program"]:
        summary["largest_sound"][k] = max(r["program"][k] for r in rows)
        for kind in rows[0]:
            if isinstance(rows[0][kind], dict) and kind not in (
                    "program", "metrics") and k in rows[0][kind]:
                summary["smallest"].setdefault(kind, {})[k] = min(
                    r[kind][k] for r in rows)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
