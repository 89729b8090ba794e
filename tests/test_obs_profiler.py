"""Spans on the profiler's clock: ``obs.span`` is a ``jax.profiler``
annotation at every level, ``update_model`` and the serving worker open
spans at their layer boundaries, each garbage collection is a ``gc`` span,
and the counters the program keeps where the work happens
(``model.last_update``, ticket stamps) agree with the fits that made
them."""

import contextlib
import gc
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import streaming, vmp
from repro.data import synthetic as syn
from repro.data.stream import Attribute, DataStream, REAL
from repro.obs import trace as obs_trace
from repro.pgm_models.static import GaussianMixture
from repro.serve.queue import AsyncPGMServer


@contextlib.contextmanager
def _level(tmp_path, level):
    path = str(tmp_path / "events.jsonl")
    prev = obs.configure(level=level, path=path)
    try:
        yield path
    finally:
        obs.configure(level=prev["level"], path=prev["path"])


@contextlib.contextmanager
def _profiled(tmp_path):
    """Run the body under a CPU ``jax.profiler`` trace; the yielded list
    is filled on exit with ``(name, start_ns, end_ns, thread)`` of every
    host event."""
    from jax.profiler import ProfileData

    logdir = str(tmp_path / "prof")
    events = []
    jax.profiler.start_trace(logdir)
    try:
        yield events
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.end_ns, line.name)
                           for e in line.events]


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_span_lands_on_the_host_plane_nested_in_its_parent(tmp_path):
    with _level(tmp_path, "off"), _profiled(tmp_path) as events:
        with obs.span("test.outer", tag="x"):
            with obs.span("test.inner") as sp:
                jnp.ones(8).block_until_ready()
                sp.add(ignored=1)
    (outer,) = _named(events, "test.outer")       # the bare name: no attrs
    (inner,) = _named(events, "test.inner")
    assert _inside(inner, outer) and inner[3] == outer[3]


def test_span_below_trace_writes_no_jsonl(tmp_path):
    with _level(tmp_path, "basic") as path, _profiled(tmp_path) as events:
        with obs.span("test.basic") as sp:
            assert sp.span_id is None
            sp.add(extra=1)                        # no-op, not an error
        obs.emit("metric", name="x", value=1.0)
    assert _named(events, "test.basic")
    counts = obs.validate_obs_events(path)
    assert "span" not in counts and counts["metric"] == 1


def test_span_at_trace_level_is_timed_and_annotated(tmp_path):
    with _level(tmp_path, "trace") as path, _profiled(tmp_path) as events:
        with obs.span("test.timed", tag="y") as sp:
            pass
    assert sp.span_id is not None and sp.dur_us >= 0
    assert _named(events, "test.timed")
    with open(path) as fh:
        (ev,) = [json.loads(l) for l in fh if '"span"' in l]
    assert ev["name"] == "test.timed" and ev["tag"] == "y"


def test_span_off_path_is_one_shared_object(tmp_path):
    with _level(tmp_path, "off"):
        assert obs.span("a") is obs.span("b", k=1)
    assert not (tmp_path / "events.jsonl").exists()


def test_gc_span_under_a_trace(tmp_path):
    assert gc.callbacks.count(obs_trace._gc_span) == 1
    gc.collect()                                   # no profiler: no span
    with _profiled(tmp_path) as events:
        with obs.span("test.collect"):
            gc.collect()
    (outer,) = _named(events, "test.collect")
    assert any(_inside(e, outer) for e in _named(events, "gc"))


def _gmm(seed=0):
    attrs = [Attribute(f"X{i}", REAL) for i in range(3)]
    return GaussianMixture(attrs, n_states=2, seed=seed)


def _points(n=600, seed=1):
    rng = np.random.default_rng(seed)
    centers = np.array([[-3.0, 0.0, 2.0], [3.0, 1.0, -2.0]], np.float32)
    z = rng.integers(0, 2, n)
    return (centers[z] + rng.normal(size=(n, 3))).astype(np.float32)


def _chunked(x, parts):
    attrs = [Attribute(f"X{i}", REAL) for i in range(x.shape[1])]
    chunks = [(c, np.zeros((len(c), 0), np.int32))
              for c in np.split(x, parts)]
    return DataStream(attrs, lambda: iter(chunks), n_instances=len(x))


UPDATE_CHILDREN = ("update_model.ingest", "update_model.dispatch",
                   "update_model.wait")


@pytest.mark.parametrize("path", ["array", "stream"])
def test_update_model_spans(tmp_path, path):
    model = _gmm()
    x = _points()
    data = x if path == "array" else _chunked(x, 3)
    model.update_model(data, sweeps=5)             # compile outside
    with _profiled(tmp_path) as events:
        model.update_model(data, sweeps=5)
    (root,) = _named(events, "update_model")
    for child in UPDATE_CHILDREN:
        (ev,) = _named(events, child)
        assert _inside(ev, root) and ev[3] == root[3]
    starts = [_named(events, c)[0][1] for c in UPDATE_CHILDREN]
    assert starts == sorted(starts)               # ingest, dispatch, wait


def test_last_update_matches_vmp_fit():
    model = _gmm()
    x = _points()
    prior, init = model._chained_prior, model.posterior
    model.update_model(x, sweeps=30, tol=1e-6)
    ref = vmp.vmp_fit(model.cp, prior, init, jnp.asarray(x),
                      jnp.zeros((len(x), 0), jnp.int32), 30, 1e-6,
                      jnp.ones(len(x)), model.backend, model.chunk)
    lu = model.last_update
    assert isinstance(lu.sweeps, jax.Array) and isinstance(lu.passes,
                                                           jax.Array)
    assert int(lu.sweeps) == int(ref.sweep) > 1
    assert int(lu.passes) == int(lu.sweeps)       # one pass per sweep
    assert lu.drifted is None and int(lu.instances) == len(x)


def test_last_update_matches_stream_fit():
    x = np.concatenate([_points(400, seed=2),
                        _points(400, seed=3)[:, ::-1] * 3.0])
    model = _gmm()
    base, chained, init = model.prior, model._chained_prior, model.posterior
    model.update_model(_chunked(x, 4), sweeps=20, tol=1e-5)
    xcs = jnp.asarray(np.stack(np.split(x, 4)))
    _, info = streaming.stream_fit(
        model.cp, base, streaming.stream_init(chained, init), xcs,
        jnp.zeros(xcs.shape[:2] + (0,), jnp.int32), sweeps=20, tol=1e-5,
        backend=model.backend, chunk=model.chunk)
    lu = model.last_update
    for field in ("sweeps", "passes", "drifted", "instances"):
        assert isinstance(getattr(lu, field), jax.Array), field
    np.testing.assert_array_equal(np.asarray(lu.sweeps), info["sweeps"])
    np.testing.assert_array_equal(np.asarray(lu.drifted), info["drifted"])
    # one scoring pass per batch before its sweeps
    np.testing.assert_array_equal(np.asarray(lu.passes),
                                  np.asarray(info["sweeps"]) + 1)
    assert float(lu.instances) == len(x)


def test_ticket_stamps_split_queue_wait_and_flush():
    bn = syn.random_discrete_bn(5, card=2, max_parents=2, seed=0)
    names = [v.name for v in bn.order]
    # no flush before stop(): the first 6 are queued, the rest shed
    with AsyncPGMServer(bn, mode="exact", max_batch=64, max_delay_ms=10_000,
                        default_deadline_ms=60_000, max_queue=6) as srv:
        tickets = [srv.submit(names[-1], {names[0]: float(i % 2)})
                   for i in range(12)]
    assert all(t.done() for t in tickets)         # stop() drained them
    answered = [t for t in tickets if t.error is None]
    shed = [t for t in tickets if t.trigger == "shed"]
    assert len(answered) == len(shed) == 6
    for t in answered:
        assert t.submitted_s <= t.flush_s <= t.done_s
    assert all(t.flush_s is None for t in shed)


def test_layers_carry_their_device_scopes():
    model = _gmm()
    x = jnp.asarray(_points(64))
    xd = jnp.zeros((64, 0), jnp.int32)
    fit = vmp.vmp_fit.lower(model.cp, model.prior, model.posterior, x, xd,
                            5, 1e-5, jnp.ones(64), model.backend,
                            model.chunk).compile().as_text()
    assert "vmp.local_step" in fit and "vmp.global_update" in fit
    state = streaming.stream_init(model.prior, model.posterior)
    scan = streaming._stream_fit_scan.lower(
        model.cp, model.prior, state, x.reshape(2, 32, 3),
        xd.reshape(2, 32, 0), jnp.ones((2, 32)), sweeps=5, tol=1e-5,
        drift_threshold=5.0, forget=0.3, backend=model.backend,
        chunk=None).compile().as_text()
    assert "streaming.drift" in scan and "vmp.local_step" in scan
