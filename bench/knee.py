#!/usr/bin/env python3
"""Sweep the offered rate of a serving cell once, to find its knee.

    python3 bench/knee.py --workload gmm_large.serve --rates 1000,2000,4000 \
        [--seconds 10] [--seed 1]

Runs the cell at each offered rate in one process and prints, per rate, the
answered rate, the 95th percentile latency from the scheduled send, the
answers within the deadline per second and the generator's lateness (on
standard error).  The knee is the highest offered rate at which the
answered rate keeps up with the offered rate and the 95th percentile stays
inside the deadline; the cell's traffic file then fixes its rate at four
fifths of it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for rate in [float(r) for r in args.rates.split(",")]:
        res = run.run_cell(args.workload, args.seed, args.seconds, False,
                           traffic_overrides={"rate_qps": rate})
        m = {k: v["value"] for k, v in res["metrics"].items()}
        print(json.dumps({"rate_qps": rate, "attempted": res["attempted"],
                          "failed": res["failed"], "correct": res["correct"],
                          **m}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
