"""The obs aggregation tier: metrics registry (counters / gauges /
log-bucketed histograms with exact-rank quantiles), snapshot merging,
the Prometheus exporter, replica health scoring, and the
degraded-replica dispatch bias in ``AsyncPGMServer`` — plus the span
error-stamping regression test and the off-vs-trace bit-identity of the
new serving paths."""

import contextlib
import json
import math
import time

import numpy as np
import pytest

from repro.data import synthetic as syn
from repro.obs import agg, export, sink
from repro.obs.health import HealthTracker
from repro.resilience.faultinject import FaultInjector
from repro.serve.queue import AsyncPGMServer


@contextlib.contextmanager
def _obs_to(tmp_path, level="basic"):
    path = str(tmp_path / "events.jsonl")
    prev = sink.configure(level=level, path=path, reset_counters=True)
    try:
        yield path
    finally:
        sink.configure(level=prev["level"], path=prev["path"],
                       reset_counters=True)


def _events(path):
    with open(path) as fh:
        return [json.loads(l) for l in fh if l.strip()]


# ---------------------------------------------------------------------------
# histogram quantiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dist", ["lognormal", "uniform", "exponential"])
def test_histogram_quantiles_match_numpy_percentile(dist):
    rng = np.random.default_rng(0)
    draws = {"lognormal": lambda: rng.lognormal(1.0, 1.0, 5000),
             "uniform": lambda: rng.uniform(0.01, 50.0, 5000),
             "exponential": lambda: rng.exponential(3.0, 5000)}[dist]()
    h = agg.Histogram("h")
    for v in draws:
        h.record(v)
    for q in (0.1, 0.25, 0.5, 0.9, 0.95, 0.99):
        got = h.quantile(q)
        want = float(np.percentile(draws, 100 * q))
        # exact-rank within one log bucket: relative error bounded by the
        # bucket width (growth - 1), with slack for rank-vs-interpolation
        assert abs(got - want) / want < h.growth - 1.0 + 0.02, \
            f"q={q}: {got} vs numpy {want}"


def test_histogram_edges_nan_and_empty():
    h = agg.Histogram("h", lo=1.0, hi=16.0, growth=2.0)
    assert h.n_bins == 4
    h.record(float("nan"))                     # ignored, never poisons
    assert h.count == 0
    assert math.isnan(h.quantile(0.5))
    h.record(0.25)                             # underflow -> exact min
    h.record(100.0)                            # overflow -> exact max
    assert h.count == 2
    assert h.quantile(0.0) == 0.25
    assert h.quantile(1.0) == 100.0


def test_counter_and_gauge():
    reg = agg.MetricsRegistry()
    c = reg.counter("reqs_total", mode="exact")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    assert reg.counter("reqs_total", mode="exact") is c   # same instrument
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("score", worker=0)
    g.set(0.75)
    assert g.value == 0.75 and g.updated > 0


# ---------------------------------------------------------------------------
# snapshot merge
# ---------------------------------------------------------------------------


def _reg_with(seed, n=200):
    rng = np.random.default_rng(seed)
    reg = agg.MetricsRegistry()
    reg.counter("c_total", leg=str(seed % 2)).inc(seed + 1)
    g = reg.gauge("g")
    g.set(float(seed))
    h = reg.histogram("lat_ms")
    for v in rng.lognormal(0.5, 1.0, n):
        h.record(v)
    return reg


def test_snapshot_merge_associativity_and_counts():
    a, b, c = (_reg_with(s).snapshot() for s in (1, 2, 3))
    left = agg.merge_snapshots(agg.merge_snapshots(a, b), c)
    right = agg.merge_snapshots(a, agg.merge_snapshots(b, c))
    assert left == right
    hist = [e for e in left["metrics"] if e["kind"] == "histogram"][0]
    assert hist["count"] == 600
    # merged quantile equals the quantile over the pooled draws
    pooled = np.concatenate([np.random.default_rng(s).lognormal(0.5, 1.0, 200)
                             for s in (1, 2, 3)])
    got = agg.quantile_from_snapshot(hist, 0.5)
    want = float(np.percentile(pooled, 50))
    assert abs(got - want) / want < hist["growth"] - 1.0 + 0.02
    # counters added; the gauge kept the newest write (seed 3 set last)
    csum = sum(e["value"] for e in left["metrics"] if e["kind"] == "counter")
    assert csum == (1 + 1) + (2 + 1) + (3 + 1)
    gauge = [e for e in left["metrics"] if e["kind"] == "gauge"][0]
    assert gauge["value"] == 3.0


def test_merge_rejects_mismatched_bucket_configs():
    r1, r2 = agg.MetricsRegistry(), agg.MetricsRegistry()
    r1.histogram("h", growth=1.15).record(1.0)
    r2.histogram("h", growth=2.0).record(1.0)
    with pytest.raises(ValueError, match="bucket configs differ"):
        agg.merge_snapshots(r1.snapshot(), r2.snapshot())


# ---------------------------------------------------------------------------
# exporter (golden output)
# ---------------------------------------------------------------------------


def test_prometheus_text_golden():
    reg = agg.MetricsRegistry()
    reg.counter("kernel_dispatch_total", kernel="k:einsum").inc(2)
    reg.gauge("replica_score", worker=0).set(0.5)
    h = reg.histogram("lat_ms", lo=1.0, hi=16.0, growth=2.0, route="a")
    for v in (1.5, 3.0, 20.0):
        h.record(v)
    assert export.prometheus_text(reg.snapshot()) == (
        '# TYPE kernel_dispatch_total counter\n'
        'kernel_dispatch_total{kernel="k:einsum"} 2\n'
        '# TYPE replica_score gauge\n'
        'replica_score{worker="0"} 0.5\n'
        '# TYPE lat_ms histogram\n'
        'lat_ms_bucket{route="a",le="2.0"} 1\n'
        'lat_ms_bucket{route="a",le="4.0"} 2\n'
        'lat_ms_bucket{route="a",le="+Inf"} 3\n'
        'lat_ms_sum{route="a"} 24.5\n'
        'lat_ms_count{route="a"} 3\n')


# ---------------------------------------------------------------------------
# span error stamping (regression: a raising body must not look clean)
# ---------------------------------------------------------------------------


def test_span_error_stamped_and_reraised(tmp_path):
    from repro import obs

    with _obs_to(tmp_path, level="trace") as path:
        with pytest.raises(KeyError):
            with obs.span("boom.region", tag="x"):
                raise KeyError("inner failure")
        spans = [e for e in _events(path) if e["event"] == "span"]
    assert len(spans) == 1
    assert spans[0]["name"] == "boom.region"
    assert spans[0]["error"] == "KeyError"
    assert spans[0]["tag"] == "x"
    assert spans[0]["dur_us"] >= 0


def test_configure_reset_clears_default_registry(tmp_path):
    agg.REGISTRY.counter("leftover_total").inc()
    with _obs_to(tmp_path):
        assert agg.REGISTRY.snapshot() == {"metrics": []}


# ---------------------------------------------------------------------------
# health tracker (unit)
# ---------------------------------------------------------------------------


def test_health_tracker_scoring_and_defer():
    tr = HealthTracker(2, alpha=0.5, threshold=0.5, min_flushes=3)
    assert tr.scores() == [1.0, 1.0]
    assert not tr.should_defer(0)              # cold replicas never defer
    for _ in range(5):
        tr.record_flush(0, 100.0)              # slow replica
        tr.record_flush(1, 1.0)                # healthy replica
    s = tr.scores()
    assert s[1] == 1.0 and s[0] < 0.05
    assert tr.should_defer(0) and not tr.should_defer(1)
    snaps = tr.snapshots()
    assert snaps[0]["degraded"] and not snaps[1]["degraded"]
    assert snaps[0]["flushes"] == 5
    # errors sink the score even at equal latency
    tr2 = HealthTracker(2, alpha=0.5, threshold=0.5, min_flushes=1)
    for _ in range(4):
        tr2.record_flush(0, 1.0, error=True)
        tr2.record_flush(1, 1.0)
    assert tr2.should_defer(0)
    assert tr2.snapshots()[0]["errors"] == 4


def test_health_lone_replica_and_uniform_sickness_never_defer():
    lone = HealthTracker(1)
    for _ in range(5):
        lone.record_flush(0, 500.0, error=True)
    assert not lone.should_defer(0)
    both = HealthTracker(2, min_flushes=1)
    for _ in range(5):
        both.record_flush(0, 500.0, error=True)
        both.record_flush(1, 500.0, error=True)
    assert not both.should_defer(0) and not both.should_defer(1)


# ---------------------------------------------------------------------------
# serving integration: degraded replica drains, SLO events, exports
# ---------------------------------------------------------------------------


def _discrete_bn(seed=0):
    return syn.random_discrete_bn(5, card=2, max_parents=2, seed=seed)


def _q(bn, i=0):
    names = [v.name for v in bn.order]
    return names[-1], {names[0]: float(i % 2)}


def test_slow_flush_drops_health_score_and_biases_dispatch(tmp_path):
    bn = _discrete_bn()
    inj = FaultInjector()
    with _obs_to(tmp_path, level="trace") as path:
        srv = AsyncPGMServer(bn, mode="exact", max_batch=8, max_delay_ms=5,
                             default_deadline_ms=60_000, replicas=2,
                             supervise_interval_ms=5)
        srv.submit(*_q(bn)).result(timeout=120)          # warm the plan
        # n is effectively unbounded so the stall cannot run dry before the
        # degraded state is observed on a slow/contended machine
        inj.slow_flush(srv, delay_s=0.08, n=1000, widx=0)
        # phase 1: trickle queries until the stalls have degraded worker 0
        # (adaptive — how fast it racks up flushes depends on scheduling)
        tickets = []
        deadline = time.monotonic() + 30.0
        i = 0
        while time.monotonic() < deadline:
            tickets.append(srv.submit(*_q(bn, i)))
            i += 1
            time.sleep(0.006)
            if srv.health.snapshots()[0]["degraded"]:
                break
        assert srv.health.snapshots()[0]["degraded"], \
            "slow replica never marked degraded"
        # phase 2: more traffic — dispatch must now bias toward worker 1
        for j in range(30):
            tickets.append(srv.submit(*_q(bn, j)))
            time.sleep(0.006)
        # snapshot BEFORE stop(): the drain deliberately disables deferral
        # (never strand a ticket), so the sick replica may catch up on fast
        # flushes during the drain and partially recover its score
        h = srv.health.snapshots()
        srv.stop()
        st = srv.stats()
        # zero lost tickets: every submit resolved with a result
        assert st["pending"] == 0
        for t in tickets:
            assert t.done() and t.error is None
            assert t.result() is not None
        # the stalled replica's score collapsed and it flushed measurably
        # fewer buckets than its healthy peer
        assert h[0]["degraded"] and not h[1]["degraded"]
        assert h[0]["score"] < 0.5 * h[1]["score"]
        assert h[0]["flushes"] < h[1]["flushes"]
        # JSONL: serve_health + slo events present and schema-valid
        counts = sink.validate_obs_events(path)
        assert counts.get("serve_health", 0) >= 2
        assert counts.get("slo", 0) >= 1
        slo = [e for e in _events(path) if e["event"] == "slo"][-1]
        assert slo["p50_ms"] <= slo["p95_ms"] <= slo["p99_ms"]
        assert 0.0 <= slo["miss_rate"] <= 1.0
        # the run exports: the Prometheus snapshot renders
        text = export.prometheus_text(agg.REGISTRY.snapshot())
        assert "serve_request_ms_bucket" in text
        assert "replica_score" in text


def test_serve_with_health_off_vs_trace_bit_identical(tmp_path):
    bn = _discrete_bn()

    def run():
        srv = AsyncPGMServer(bn, mode="exact", max_batch=4, max_delay_ms=2,
                             default_deadline_ms=60_000, replicas=2)
        tickets = [srv.submit(*_q(bn, i)) for i in range(12)]
        out = [np.asarray(t.result(timeout=120)) for t in tickets]
        srv.stop()
        return out

    prev = sink.configure(level="off", reset_counters=True)
    try:
        base = run()
        with _obs_to(tmp_path, level="trace"):
            traced = run()
    finally:
        sink.configure(level=prev["level"], path=prev["path"],
                       reset_counters=True)
    for a, b in zip(base, traced):
        assert np.array_equal(a, b)            # bit-identical, not allclose


def test_serve_off_level_emits_no_events_or_metrics(tmp_path):
    bn = _discrete_bn()
    path = str(tmp_path / "off.jsonl")
    prev = sink.configure(level="off", path=path, reset_counters=True)
    try:
        srv = AsyncPGMServer(bn, mode="exact", max_batch=4, max_delay_ms=2,
                             default_deadline_ms=60_000, replicas=2)
        [t.result(timeout=120) for t in
         [srv.submit(*_q(bn, i)) for i in range(8)]]
        srv.stop()
        assert not (tmp_path / "off.jsonl").exists()
        # no SLO instrument was ever created with obs off
        names = {e["name"] for e in agg.REGISTRY.snapshot()["metrics"]}
        assert "serve_request_ms" not in names
    finally:
        sink.configure(level=prev["level"], path=prev["path"],
                       reset_counters=True)
