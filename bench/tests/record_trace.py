#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reads.

    python3 bench/tests/record_trace.py [DEST]   (on the chip)

Two ``update_model`` calls of ``gmm_large`` on 2^14 instances, warmed up
first, inside the harness's ``bench.window`` and ``bench.update_model``
spans, traced as the harness traces; writes DEST (by default
``bench/tests/data/learn_small.xplane.pb``).
"""

import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    import jax
    import numpy as np

    from bench.drivers.learn_closed import attributes
    from bench.gen import host_batches
    from bench.run import profile_options, read_json
    from repro.pgm_models.static import GaussianMixture

    cfg = read_json(ROOT / "bench" / "configs" / "gmm_large.json")
    xc, _ = host_batches(cfg, {}, 7, 2, 1 << 14)
    model = GaussianMixture(attributes(cfg), n_states=4, seed=7)
    model.update_model(np.asarray(xc[0]))
    out = tempfile.mkdtemp()
    jax.profiler.start_trace(out, profiler_options=profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for x in xc:
            with jax.profiler.TraceAnnotation("bench.update_model"):
                model.update_model(x)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                        recursive=True)
    dest = (sys.argv[1] if len(sys.argv) > 1
            else ROOT / "bench" / "tests" / "data" / "learn_small.xplane.pb")
    shutil.copy(path, dest)
    print(dest, os.path.getsize(dest))


if __name__ == "__main__":
    main()
