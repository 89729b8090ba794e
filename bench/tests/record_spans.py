#!/usr/bin/env python3
"""Record the chip trace with the program's spans that
``test_bench_spans.py`` reads, and the program's counters of each call.

    python3 bench/tests/record_spans.py [DIR]   (on the chip)

``gmm_large`` on batches of 2^13 instances: two ``update_model`` calls on
one array each (the ``vmp_fit`` path), then one on a ``DataStream`` of two
batches (the ``stream_fit`` path), the sequence warmed up first, each call
inside the harness's ``bench.update_model`` span within ``bench.window``,
traced as the harness traces.  After the trace each call's
``model.last_update`` is read; writes ``learn_spans.xplane.pb`` and
``learn_spans.json`` into DIR (by default ``bench/tests/data``).
"""

import glob
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

BATCH = 1 << 13


def main():
    import jax
    import numpy as np

    from bench.drivers.learn_closed import attributes
    from bench.gen import host_batches
    from bench.run import profile_options, read_json
    from repro.data.stream import DataStream
    from repro.pgm_models.static import GaussianMixture

    cfg = read_json(ROOT / "bench" / "configs" / "gmm_large.json")
    xc, _ = host_batches(cfg, {}, 11, 4, BATCH)
    xc = [np.asarray(x) for x in xc]
    attrs = attributes(cfg)
    empty = np.zeros((BATCH, 0), np.int32)

    def stream(a, b):
        chunks = [(a, empty), (b, empty)]
        return DataStream(attrs, lambda: iter(chunks),
                          n_instances=2 * BATCH)

    calls = [("array", xc[0]), ("array", xc[1]), ("stream", stream(*xc[2:]))]
    model = GaussianMixture(attrs, n_states=cfg["latent_card"], seed=11)
    for _ in range(2):      # every shape, and the array call after a stream
        for _, data in calls:
            model.update_model(data)
    out = tempfile.mkdtemp()
    counters = []
    jax.profiler.start_trace(out, profiler_options=profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for _, data in calls:
            with jax.profiler.TraceAnnotation("bench.update_model"):
                model.update_model(data)
            counters.append(model.last_update)
    jax.profiler.stop_trace()

    def host(a):
        return None if a is None else np.asarray(a).ravel().tolist()

    record = {"config": "gmm_large", "batch": BATCH,
              "device_kind": jax.devices()[0].device_kind,
              "calls": [dict(feed=feed, sweeps=host(c.sweeps),
                             passes=host(c.passes), drifted=host(c.drifted),
                             instances=int(c.instances))
                        for (feed, _), c in zip(calls, counters)]}
    dest = Path(sys.argv[1]) if len(sys.argv) > 1 else (
        ROOT / "bench" / "tests" / "data")
    dest.mkdir(parents=True, exist_ok=True)
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, dest / "learn_spans.xplane.pb")
    (dest / "learn_spans.json").write_text(json.dumps(record, indent=1))
    print(json.dumps(record))


if __name__ == "__main__":
    main()
