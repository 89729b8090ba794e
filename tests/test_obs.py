"""Observability: JSONL sink + schema validation, span tracing, streaming
metrics (drift events on a concept switch), serve-path telemetry — and
the zero-overhead guarantee that ``REPRO_OBS=off`` leaves every numeric
output bit-identical and emits nothing."""

import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import streaming, vmp
from repro.core.dag import PlateSpec
from repro.data import synthetic as syn
from repro.data.stream import DataStream


@contextlib.contextmanager
def _obs_to(tmp_path, level="trace"):
    """Route obs events to a temp JSONL file at ``level``; restore the
    previous config on exit (tests must not assume the ambient level that
    ``REPRO_OBS`` may set)."""
    path = str(tmp_path / "events.jsonl")
    prev = obs.configure(level=level, path=path, reset_counters=True)
    try:
        yield path
    finally:
        obs.configure(level=prev["level"], path=prev["path"],
                      reset_counters=True)


def _events(path):
    with open(path) as fh:
        return [json.loads(l) for l in fh if l.strip()]


def _gmm_setup(n=1000, batch=250, seed=7):
    stream, _, _ = syn.gmm_stream(n, 2, 3, seed=seed)
    spec = PlateSpec(n_features=3, latent_card=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    batches = list(stream.batches(batch))
    xcs = jnp.stack([b.xc for b in batches])
    xds = jnp.stack([b.xd for b in batches])
    masks = jnp.stack([b.mask for b in batches])
    return cp, prior, init, xcs, xds, masks


# ---------------------------------------------------------------------------
# stream_fit: off is a bit-identical no-op; trace emits schema-valid events
# ---------------------------------------------------------------------------


def test_stream_fit_off_bit_identical_and_trace_emits(tmp_path):
    cp, prior, init, xcs, xds, masks = _gmm_setup()

    with _obs_to(tmp_path, level="off") as path_off:
        s_off = streaming.stream_init(prior, init)
        s_off, info_off = streaming.stream_fit(cp, prior, s_off,
                                               xcs, xds, masks)
        assert not (tmp_path / "events.jsonl").exists(), \
            "REPRO_OBS=off must never open the sink"

    with _obs_to(tmp_path, level="trace") as path:
        s_on = streaming.stream_init(prior, init)
        s_on, info_on = streaming.stream_fit(cp, prior, s_on,
                                             xcs, xds, masks)
        counts = obs.validate_obs_events(path)

    # same device program either way -> bit-identical outputs
    assert np.array_equal(np.asarray(s_off.post.reg.m),
                          np.asarray(s_on.post.reg.m))
    for k in info_off:
        assert np.array_equal(np.asarray(info_off[k]),
                              np.asarray(info_on[k])), k

    T = xcs.shape[0]
    assert counts["stream_batch"] == T
    evs = [e for e in _events(path) if e["event"] == "stream_batch"]
    assert [e["t"] for e in evs] == list(range(T))
    np.testing.assert_allclose([e["elbo"] for e in evs],
                               np.asarray(info_on["elbo"]), rtol=1e-6)
    # in-graph gauges made it out: sweeps-to-convergence and n_eff
    assert all(1 <= e["sweeps"] <= 20 for e in evs)
    assert sum(e["n_eff"] for e in evs) == 1000.0


def test_stream_fit_info_has_metric_columns():
    """The info dict carries every StreamBatchMetrics column with leading
    dim T (the per-batch drift-event mask is part of the fit result)."""
    cp, prior, init, xcs, xds, masks = _gmm_setup(n=500, batch=250)
    state = streaming.stream_init(prior, init)
    _, info = streaming.stream_fit(cp, prior, state, xcs, xds, masks)
    for k in ("elbo", "score", "ph", "drifted", "n_eff", "rho", "sweeps"):
        assert k in info and np.asarray(info[k]).shape[0] == xcs.shape[0], k
    assert not np.asarray(info["drifted"]).any()      # stationary stream
    assert (np.asarray(info["rho"]) == 1.0).all()     # no tempering


# ---------------------------------------------------------------------------
# drift events fire on the bn_stream concept switch (satellite a)
# ---------------------------------------------------------------------------


def test_drift_events_fire_on_bn_stream_concept_switch(tmp_path):
    """Generator switches mid-stream (two different CLG trees); the PH
    test fires after the switch and the firing batches surface both in
    the per-batch ``drifted`` mask and as ``drift`` JSONL events."""
    bn_a = syn.clg_tree_bn(3, seed=0)
    bn_b = syn.clg_tree_bn(3, seed=11, beta_lo=2.0, beta_hi=3.0)
    stream = DataStream.concat([syn.bn_stream(bn_a, 1500, seed=1),
                                syn.bn_stream(bn_b, 1500, seed=2)])
    spec = PlateSpec(n_features=3, latent_card=1)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    batches = list(stream.batches(250))
    xcs = jnp.stack([b.xc for b in batches])
    xds = jnp.stack([b.xd for b in batches])
    masks = jnp.stack([b.mask for b in batches])

    with _obs_to(tmp_path, level="basic") as path:
        state = streaming.stream_init(prior, init)
        state, info = streaming.stream_fit(cp, prior, state, xcs, xds, masks,
                                           drift_threshold=3.0)
        counts = obs.validate_obs_events(path)

    flags = np.asarray(info["drifted"])
    switch_at = 1500 // 250
    assert flags.any(), "drift never fired on the concept switch"
    assert not flags[:switch_at].any(), "drift fired before the switch"
    assert int(state.n_drifts) == int(flags.sum())

    drift_evs = [e for e in _events(path) if e["event"] == "drift"]
    assert counts["drift"] == int(flags.sum())
    assert [e["t"] for e in drift_evs] == list(np.flatnonzero(flags))
    assert all(e["ph"] > 3.0 for e in drift_evs)


# ---------------------------------------------------------------------------
# PGMQueryEngine telemetry (satellite d)
# ---------------------------------------------------------------------------


def _exact_engine():
    from repro.serve.engine import PGMQueryEngine

    bn = syn.random_discrete_bn(4, card=3, seed=0, tree=True)
    return PGMQueryEngine(bn, mode="exact")


def test_serve_exact_telemetry(tmp_path):
    with _obs_to(tmp_path, level="trace") as path:
        eng = _exact_engine()
        eng.submit("D0", {"D2": 1, "D3": 2})
        eng.submit("D0", {"D2": 0, "D3": 0})
        eng.submit("D0", {"D3": 1})                 # second schema bucket
        done = eng.flush()
        # same schema at the same batch size -> the AOT executable is
        # reused (the cache key is (schema, batch, dtypes))
        eng.submit("D0", {"D2": 2, "D3": 1})
        eng.submit("D0", {"D2": 1, "D3": 0})
        eng.flush()
        counts = obs.validate_obs_events(path)
        evs = _events(path)

    assert len(done) == 3 and all(q.done for q in done)
    assert counts["serve_flush"] == 2
    assert counts["serve_bucket"] == 3
    assert counts["jt_plan"] == 2          # one per compiled (schema, batch)

    buckets = [e for e in evs if e["event"] == "serve_bucket"]
    by_schema = {}
    for b in buckets:
        by_schema.setdefault(b["schema"], []).append(b)
    assert by_schema["D2,D3"][0]["batch"] == 2
    assert by_schema["D2,D3"][0]["cache_hit"] is False
    assert by_schema["D2,D3"][0]["compile_us"] > 0
    assert by_schema["D2,D3"][1]["cache_hit"] is True   # AOT cache reused
    assert by_schema["D2,D3"][1]["compile_us"] == 0
    assert all(b["latency_us"] > 0 and b["execute_us"] >= 0 for b in buckets)
    assert {b["queue_depth"] for b in buckets} == {3, 2}

    # span nesting: flush spans are roots, bucket/build/compile/execute
    # have parents; a plan compile runs inside the cache miss's build
    spans = {e["span_id"]: e for e in evs if e["event"] == "span"}
    names = [s["name"] for s in spans.values()]
    for n in ("serve.flush", "serve.bucket", "serve.plan.build",
              "jt.compile", "jt.execute"):
        assert n in names, n
    assert names.count("serve.plan.build") == 2    # one per compiled plan
    for s in spans.values():
        if s["name"] == "serve.flush":
            assert s["parent_id"] is None
        elif s["name"] == "serve.bucket":
            assert spans[s["parent_id"]]["name"] == "serve.flush"
        elif s["name"] == "jt.compile":
            assert spans[s["parent_id"]]["name"] == "serve.plan.build"
        else:   # serve.plan.build / jt.execute nest under their bucket
            assert spans[s["parent_id"]]["name"] == "serve.bucket"


def test_serve_off_no_events_and_identical_posteriors(tmp_path):
    queries = [("D0", {"D2": 1, "D3": 2}), ("D0", {"D2": 0, "D3": 0})]

    with _obs_to(tmp_path, level="off"):
        eng = _exact_engine()
        qs_off = [eng.submit(t, e) for t, e in queries]
        eng.flush()
        assert not (tmp_path / "events.jsonl").exists()

    with _obs_to(tmp_path, level="trace") as path:
        eng = _exact_engine()
        qs_on = [eng.submit(t, e) for t, e in queries]
        eng.flush()
        assert obs.validate_obs_events(path)["serve_bucket"] == 1

    for a, b in zip(qs_off, qs_on):
        assert np.array_equal(a.result, b.result)
        assert a.log_evidence == b.log_evidence


def test_serve_vmp_mode_telemetry(tmp_path):
    from repro.pgm_models import GaussianMixture
    from repro.serve.engine import PGMQueryEngine

    s, _, _ = syn.gmm_stream(600, 3, 4, seed=1)
    m = GaussianMixture(s.attributes, n_states=3)
    m.update_model(s)
    batch = s.collect()

    with _obs_to(tmp_path, level="trace") as path:
        eng = PGMQueryEngine(m, mode="vmp")
        for b in range(3):
            eng.submit("Z", {f"X{i}": float(batch.xc[b, i])
                             for i in range(4)})
        eng.flush()
        for b in range(3, 6):                       # same padded capacity
            eng.submit("Z", {f"X{i}": float(batch.xc[b, i])
                             for i in range(4)})
        done = eng.flush()
        obs.validate_obs_events(path)
        evs = _events(path)

    assert all(q.done for q in done)
    buckets = [e for e in evs if e["event"] == "serve_bucket"]
    assert len(buckets) == 2 and all(b["mode"] == "vmp" for b in buckets)
    assert buckets[0]["cache_hit"] is False
    assert buckets[1]["cache_hit"] is True     # posterior_z capacity reused
    np.testing.assert_allclose(
        np.stack([q.result for q in done]),
        np.asarray(m.posterior_z(batch))[3:6], atol=1e-5)


# ---------------------------------------------------------------------------
# with_metrics: local_step chunk gauges and the dvmp mesh path
# ---------------------------------------------------------------------------


def test_local_step_with_metrics_chunked():
    spec = PlateSpec(n_features=3, latent_card=2)
    cp = vmp.compile_plate(spec)
    post = vmp.symmetry_broken(vmp.default_prior(cp), jax.random.PRNGKey(2))
    xc = jax.random.normal(jax.random.PRNGKey(3), (300, 3))
    xd = jnp.zeros((300, 0), jnp.int32)
    mask = jnp.concatenate([jnp.ones(260), jnp.zeros(40)])

    s0, r0 = vmp.local_step(cp, post, xc, xd, mask)
    s1, r1, m1 = vmp.local_step(cp, post, xc, xd, mask, with_metrics=True)
    assert m1.chunk_n_eff.shape == (1,)
    assert float(m1.chunk_n_eff.sum()) == 260.0

    s2, r2, m2 = vmp.local_step(cp, post, xc, xd, mask, chunk=128,
                                with_metrics=True)
    assert m2.chunk_n_eff.shape == (3,)        # ceil(300/128) chunks
    assert float(m2.chunk_n_eff.sum()) == 260.0
    np.testing.assert_allclose(np.asarray(r0), np.asarray(r2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s0.local_elbo),
                               np.asarray(s2.local_elbo), rtol=1e-5)


def test_dvmp_fit_with_metrics_single_device_mesh():
    from repro.core import dvmp
    from repro.launch.mesh import make_mesh

    spec = PlateSpec(n_features=3, latent_card=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    xc = jax.random.normal(jax.random.PRNGKey(3), (128, 3))
    xd = jnp.zeros((128, 0), jnp.int32)
    mesh = make_mesh((1,), ("data",))

    ref = dvmp.dvmp_fit(cp, prior, init, xc, xd, mesh, max_sweeps=10)
    st, metrics = dvmp.dvmp_fit(cp, prior, init, xc, xd, mesh,
                                max_sweeps=10, with_metrics=True)
    assert metrics.shard_n.shape == (1,)       # one shard on a 1-device mesh
    assert float(metrics.shard_n.sum()) == 128.0
    assert int(metrics.sweeps) == int(st.sweep) >= 1
    # the metric-free program is untouched (separate cache key)
    np.testing.assert_allclose(np.asarray(ref.post.reg.m),
                               np.asarray(st.post.reg.m), atol=1e-6)


# ---------------------------------------------------------------------------
# end-to-end legs: the event types only a whole workload emits
# ---------------------------------------------------------------------------


def _temporal_leg():
    """A fused dynamic-HMM fit."""
    from repro.pgm_models import HiddenMarkovModel

    batches, attrs, _ = syn.hmm_stream(n_batches=1, s=16, t=10, states=2,
                                       f=2, shift=8.0, seed=0)
    m = HiddenMarkovModel(attrs, n_states=2, seed=0)
    assert np.isfinite(m.update_model(batches[0], sweeps=3))


def _serve_leg():
    """Timeout-triggered micro-batch flushes around a hot model swap."""
    from repro.serve.queue import AsyncPGMServer

    bn = syn.random_discrete_bn(5, card=2, max_parents=2, seed=0)
    bn2 = syn.random_discrete_bn(5, card=2, max_parents=2, seed=1)
    names = [v.name for v in bn.order]
    with AsyncPGMServer(bn, mode="exact", max_batch=64, max_delay_ms=20,
                        default_deadline_ms=60_000) as srv:
        for swap_to in (bn2, None):
            tickets = [srv.submit(names[-1], {names[0]: float(k % 2)})
                       for k in range(3)]
            for t in tickets:
                assert np.isfinite(np.asarray(t.result(timeout=120))).all()
            if swap_to is not None:
                info = srv.swap_model(swap_to)
                assert info["new_version"] == 1 and info["warmed_plans"] >= 1
    assert srv.stats()["pending"] == 0


def _shed_leg():
    """A submit over the bounded queue's capacity is shed."""
    from repro.resilience import ShedError
    from repro.serve.queue import AsyncPGMServer

    bn = syn.random_discrete_bn(5, card=2, max_parents=2, seed=0)
    names = [v.name for v in bn.order]
    with AsyncPGMServer(bn, mode="exact", max_batch=16, max_delay_ms=10_000,
                        default_deadline_ms=60_000, max_queue=2) as srv:
        kept = [srv.submit(names[-1], {names[0]: float(k % 2)})
                for k in range(2)]
        with pytest.raises(ShedError):
            srv.submit(names[-1], {names[0]: 0.0}).result()
    assert all(t.error is None for t in kept)


# leg -> (workload, the event types it must emit that no narrower test
# asserts)
_LEGS = {
    "temporal": (_temporal_leg, ("temporal_fit",)),
    "serve": (_serve_leg, ("serve_deadline", "serve_swap")),
    "chaos": (_shed_leg, ("serve_shed",)),
}


@pytest.mark.parametrize("leg", sorted(_LEGS))
def test_obs_leg_emits_its_events(tmp_path, leg):
    run, need = _LEGS[leg]
    with _obs_to(tmp_path) as path:
        run()
        counts = obs.validate_obs_events(path)
    missing = [ev for ev in need if not counts.get(ev)]
    assert not missing, (missing, counts)


# ---------------------------------------------------------------------------
# sink mechanics: spans below TRACE, validator rejects malformed streams
# ---------------------------------------------------------------------------


def test_span_null_below_trace(tmp_path):
    with _obs_to(tmp_path, level="basic") as path:
        with obs.span("should.not.emit") as sp:
            assert sp.span_id is None
            sp.add(extra=1)                    # no-op, not an error
        obs.emit("metric", name="x", value=1.0)
        counts = obs.validate_obs_events(path)
    assert "span" not in counts and counts["metric"] == 1


def _line(**kw):
    base = {"ts": 1.0, "seq": kw.pop("seq", 1), "run": "r1",
            "event": "metric", "name": "x", "value": 0}
    base.update(kw)
    return json.dumps(base)


def test_validate_obs_events_rejects_malformed():
    ok = [_line(seq=1), _line(seq=2)]
    assert obs.validate_obs_events(ok) == {"metric": 2}

    with pytest.raises(ValueError, match="invalid JSON"):
        obs.validate_obs_events(["{not json"])
    with pytest.raises(ValueError, match="unknown event"):
        obs.validate_obs_events([_line(event="nope")])
    with pytest.raises(ValueError, match="missing base field"):
        obs.validate_obs_events(['{"ts": 1.0, "seq": 1, "event": "log"}'])
    with pytest.raises(ValueError, match="missing field"):
        obs.validate_obs_events(
            ['{"ts": 1.0, "seq": 1, "run": "r", "event": "drift", "t": 0}'])
    with pytest.raises(ValueError, match="not monotone"):
        obs.validate_obs_events([_line(seq=2), _line(seq=2)])
    # independent runs keep independent seq counters
    assert obs.validate_obs_events(
        [_line(seq=5), _line(seq=3, run="r2")]) == {"metric": 2}


def test_configure_restores_previous():
    prev = obs.configure(level="basic")
    try:
        assert obs.enabled() and not obs.enabled(obs.TRACE)
        with pytest.raises(ValueError, match="unknown obs level"):
            obs.configure(level="loud")
    finally:
        obs.configure(level=prev["level"], path=prev["path"])
