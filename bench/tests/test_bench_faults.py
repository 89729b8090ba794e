"""Each fault a cell can have, planted under a whole run, makes ``correct``
false; the control (the reference in bfloat16) fails a limit; sound runs
at these sizes pass."""

import numpy as np
import pytest

from bench import calibrate, run
from bench.tests.tiny import SEED, TINY

LEARN = ["gmm_large.stream", "nb_mixed.drift"]


def tiny_run(workload, seed=SEED):
    return run.run_cell(workload, seed, 1.0, False, require_tpu=False,
                        traffic_overrides=TINY[workload])


@pytest.mark.parametrize("workload", LEARN + ["gmm_large.serve"])
def test_sound_run_is_correct(workload):
    res = tiny_run(workload)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("workload", LEARN)
def test_state_left_unchanged_fails(workload, monkeypatch):
    from repro.pgm_models.base import Model

    monkeypatch.setattr(Model, "update_model",
                        lambda self, data, **kw: -1.0e6)
    assert not tiny_run(workload)["correct"]


def halve(data):
    from repro.data.stream import Batch, DataStream

    if isinstance(data, DataStream):
        chunks = [(xc[: len(xc) // 2], xd[: len(xd) // 2])
                  for xc, xd in data.chunks()]
        return DataStream(data.attributes, lambda: iter(chunks))
    if isinstance(data, Batch):
        n = len(data.xc) // 2
        return Batch(data.xc[:n], data.xd[:n], data.mask[:n])
    return data[: len(data) // 2]


@pytest.mark.parametrize("workload", LEARN)
def test_half_batch_left_out_fails(workload, monkeypatch):
    from repro.pgm_models.base import Model

    orig = Model.update_model
    monkeypatch.setattr(Model, "update_model",
                        lambda self, data, **kw: orig(self, halve(data), **kw))
    assert not tiny_run(workload)["correct"]


def test_altered_answer_fails(monkeypatch):
    from repro.serve.engine import PGMQueryEngine

    orig = PGMQueryEngine._flush_vmp

    def swapped(self, schema, qs):
        info = orig(self, schema, qs)
        for q in qs:
            q.result = q.result[[1, 0, 2, 3]]
        return info

    monkeypatch.setattr(PGMQueryEngine, "_flush_vmp", swapped)
    assert not tiny_run("gmm_large.serve")["correct"]


@pytest.mark.parametrize("workload", LEARN + ["gmm_large.serve"])
def test_control_fails_a_limit(workload):
    import jax

    cell = run.load_cell(workload, SEED, 1.0, False,
                         traffic_overrides=TINY[workload])
    cell.devices = jax.devices()[:1]
    extra = (calibrate.serve_extra if workload.endswith("serve")
             else calibrate.learn_extra)(cell)
    ctrl = extra["control"]
    assert any(ctrl[k] > cell.limits[k] for k in ctrl), (ctrl, cell.limits)
    # and every planted fault fails a limit too
    for name, readings in extra.items():
        assert any(readings[k] > cell.limits[k] for k in readings), name
