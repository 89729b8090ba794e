"""The open-loop schedule: fixed work per seed, lateness reported, and a
query that fails or never comes counts as later than any answer."""

import time

import numpy as np
import pytest

from bench import run
from bench.drivers import serve_open
from bench.tests.tiny import SEED, TINY


def test_schedule_spans_the_window_with_a_fixed_count():
    for seed in (1, SEED):
        due, rows = serve_open.schedule(seed, 500.0, 4.0, 100)
        assert len(due) == len(rows) == 2000
        assert 0 < due[0] and due[-1] < 4.0 and np.all(np.diff(due) >= 0)
        assert rows.min() >= 0 and rows.max() < 100
    a, _ = serve_open.schedule(1, 500.0, 4.0, 100)
    b, _ = serve_open.schedule(2, 500.0, 4.0, 100)
    assert not np.allclose(a, b)


class SlowServer:
    """Takes 5 ms a submit: the sends fall behind their schedule."""

    def submit(self, target, evidence):
        time.sleep(0.005)
        return evidence


def test_lateness_is_reported():
    due = np.linspace(0.0, 0.05, 50)            # 1 ms apart
    _, tickets, late = serve_open.send(SlowServer(), due, np.arange(50),
                                       {i: i for i in range(50)})
    assert tickets == list(range(50))
    assert late[-1] > 0.1 and np.all(np.diff(late[5:]) > 0)


def test_failed_queries_are_later_than_any_answer(monkeypatch):
    from repro.serve import queue

    submit = queue.AsyncPGMServer.submit
    count = {"n": 0}

    def failing(self, target, evidence, *a, **kw):
        count["n"] += 1
        # the 200 warm-up queries pass; every fifth of the window fails
        if count["n"] > 200 and count["n"] % 5 == 0:
            t = queue.ServeTicket(-1, 0.0, time.monotonic())
            t._finish(error=RuntimeError("lost"), done_s=time.monotonic())
            return t
        return submit(self, target, evidence, *a, **kw)

    monkeypatch.setattr(queue.AsyncPGMServer, "submit", failing)
    res = run.run_cell("gmm_large.serve", SEED, 1.0, False,
                       require_tpu=False,
                       traffic_overrides=TINY["gmm_large.serve"])
    assert res["failed"] == 40 and not res["correct"]
    # a fifth failed: the 95th percentile is a failed query
    assert res["metrics"]["query_ms_p95"]["value"] == pytest.approx(61e3)
    assert res["metrics"]["query_ok_per_s"]["value"] <= 160
