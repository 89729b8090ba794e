"""The program's spans in a profiler trace: idle time split by the
innermost program span, gaps named by it, and the E-step share from the
program's own pass counter against the one from kernel launches."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import run, spans, trace
from bench.peaks import PEAKS
from bench.run import load_module

DATA = Path(__file__).parent / "data"
XPLANE = DATA / "learn_spans.xplane.pb"
COUNTERS = DATA / "learn_spans.json"


def op(name, s, e):
    return trace.Op(f"%{name} = f32[4]{{0}} fusion(...)", s, e)


def test_innermost_program_span_by_hand():
    tr = trace.Trace({}, [("bench.update_model", 0, 100),
                          ("update_model", 10, 90),
                          ("update_model.ingest", 10, 40),
                          ("gc", 20, 30),
                          ("update_model.wait", 60, 90)], (0.0, 100.0))
    assert spans.innermost_segments(tr) == [
        ("update_model.ingest", 10, 20), ("gc", 20, 30),
        ("update_model.ingest", 30, 40), ("update_model", 40, 60),
        ("update_model.wait", 60, 90)]


def test_idle_under_by_hand():
    # busy 0-15 and 50-70 of a 100 ns window; ingest 10-40 is idle 15-40,
    # the root's own stretch 40-60 is idle 40-50, wait 60-90 idle 70-90
    ops = [op("fusion.1", 0, 15), op("fusion.2", 50, 70)]
    tr = trace.Trace({"/device:TPU:0": ops},
                     [("bench.window", 0, 100), ("update_model", 10, 90),
                      ("update_model.ingest", 10, 40),
                      ("update_model.wait", 60, 90)], (0.0, 100.0))
    assert spans.idle_by_span(tr) == {"update_model.ingest": 25,
                                      "update_model": 10,
                                      "update_model.wait": 20}
    assert spans.idle_under(tr, "update_model.ingest") == pytest.approx(25)
    assert spans.idle_under(tr, "serve.plan.build") == 0
    assert spans.idle_under(trace.Trace({}, [], (0.0, 100.0)), "x") is None


def test_gaps_named_by_the_program_span():
    ops = [op("fusion.1", 0, 10), op("fusion.2", 60, 70)]
    tr = trace.Trace({"/device:TPU:0": ops},
                     [("bench.window", 0, 100),
                      ("bench.update_model", 5, 95),
                      ("update_model", 5, 95),
                      ("update_model.ingest", 12, 58),
                      ("serve.worker.flush", 71, 99)], (0.0, 100.0))
    gaps = trace.idle_gaps(tr)
    assert gaps[0] == ["update_model.ingest", pytest.approx(50e-9)]
    assert gaps[1] == ["serve.worker.flush", pytest.approx(30e-9)]


def test_estep_share_by_hand():
    cfg = {"latent_card": 4, "continuous": 10, "discrete_cards": []}
    v5e = PEAKS["TPU v5 lite"]
    one = spans.work.least_seconds(*spans.work.estep_pass(cfg, 1 << 20), v5e)
    got = spans.estep_pct(cfg, [3, 2, 2], 1 << 20, 2.0, 1, v5e)
    assert got == pytest.approx(100 * 7 * one / 2.0)


@pytest.fixture(scope="module")
def recorded():
    if not (XPLANE.exists() and COUNTERS.exists()):
        pytest.fail(f"missing {XPLANE.name}: record it with record_spans.py")
    return spans.load(str(XPLANE)), json.loads(COUNTERS.read_text())


def test_recorded_span_trace_is_small():
    assert XPLANE.stat().st_size < 4 << 20


def test_recorded_program_spans_nest_in_the_harness_calls(recorded):
    tr, rec = recorded
    outer = [(s, e) for n, s, e in tr.spans if n == "bench.update_model"]
    roots = [(s, e) for n, s, e in tr.spans if n == "update_model"]
    assert len(outer) == len(roots) == len(rec["calls"])
    for (s0, e0), (s, e) in zip(outer, roots):
        assert s0 <= s < e <= e0
        kids = [(n, a, b) for n, a, b in tr.spans
                if n.startswith("update_model.") and s <= a < b <= e]
        assert [n for n, _, _ in kids] == ["update_model.ingest",
                                           "update_model.dispatch",
                                           "update_model.wait"]


def test_recorded_idle_split_by_program_span(recorded):
    tr, _ = recorded
    ingest = spans.idle_under(tr, "update_model.ingest")
    assert 0 <= ingest <= 100
    named = 100 * sum(spans.idle_by_span(tr).values()) / 1e9 / tr.window_s
    assert ingest <= named <= trace.idle_pct(tr) + 1e-6
    gaps = trace.idle_gaps(tr)
    assert all(spans.is_program(n) or n.startswith("bench.")
               for n, _ in gaps)
    assert any(n.startswith("update_model") for n, _ in gaps)


def test_recorded_estep_share_matches_kernel_launches(recorded):
    tr, rec = recorded
    cfg = run.read_json(run.BENCH / "configs" / f"{rec['config']}.json")
    peaks = PEAKS[rec["device_kind"]]
    sweeps = [s for call in rec["calls"] for s in call["sweeps"]]
    passes = [p for call in rec["calls"] for p in call["passes"]]
    # the stream call scores each batch once before its sweeps
    assert passes == [s + (c["feed"] == "stream") for c in rec["calls"]
                      for s in c["sweeps"]]
    # one CLG Gram launch per sweep: the scoring pass uses no statistics,
    # so the compiler drops its reduction and it launches no kernel
    assert len(trace.kernel_launches(tr)) == sum(sweeps) < sum(passes)
    mfu = load_module(run.BENCH / "metrics" / "learn_mfu.py", "m_mfu_spans")
    ctx = SimpleNamespace(cell=SimpleNamespace(cfg=cfg), trace=tr,
                          window_s=tr.window_s, peaks=peaks)
    got = spans.estep_pct(cfg, sweeps, rec["batch"], tr.window_s,
                          len(tr.ops), peaks)
    assert got == pytest.approx(mfu.read(ctx), rel=0.01)
