"""Temporal hot path (fused scanned VB-EM, masks, streaming, serving).

Covers the fused/unfused parity contract for every dynamic model class,
the masked forward-backward padding semantics (left padding seeds from
the initial distribution; NaN padding is never read), factorial-HMM
structured VB against exact joint-chain inference, SLDS regime
segmentation, sequence-batch streaming with drift detection, the
compiled-program cache (no retrace across same-shape refits), and
temporal serving through ``PGMQueryEngine(mode="temporal")``.
"""

import contextlib
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.factored_frontier import (Factorial2TBN,
                                          factored_frontier_filter,
                                          factored_frontier_smooth)
from repro.data import synthetic as syn
from repro.obs import sink as obs
from repro.pgm_models import (AutoRegressiveHMM, FactorialHMMModel,
                              HiddenMarkovModel, KalmanFilter, SwitchingLDS,
                              seq_stream_fit)
from repro.pgm_models import dynamic as dyn
from repro.serve.engine import PGMQueryEngine


@contextlib.contextmanager
def _obs_to(tmp_path, level="basic"):
    path = str(tmp_path / "events.jsonl")
    prev = obs.configure(level=level, path=path, reset_counters=True)
    try:
        yield path
    finally:
        obs.configure(level=prev["level"], path=prev["path"],
                      reset_counters=True)


# ---------------------------------------------------------------------------
# masked forward-backward
# ---------------------------------------------------------------------------


def test_forward_backward_left_padding():
    """A left-padded sequence must behave exactly like its observed suffix:
    the recursion seeds from log_init at the first OBSERVED step (no
    spurious transition out of the padding) and the padded frames' loglik
    values — here NaN — are never read."""
    rng = np.random.default_rng(0)
    S, T, P = 3, 9, 3
    log_init = jnp.log(jnp.asarray([0.6, 0.3, 0.1], jnp.float32))
    tr = (0.2 * rng.dirichlet(np.ones(S), size=S)
          + 0.8 * np.eye(S)).astype(np.float32)
    log_trans = jnp.log(jnp.asarray(tr))
    ll_obs = jnp.asarray(rng.standard_normal((T - P, S)), jnp.float32)
    ll_pad = jnp.concatenate([jnp.full((P, S), jnp.nan), ll_obs])
    mask = jnp.concatenate([jnp.zeros(P), jnp.ones(T - P)])

    g_pad, xi_pad, lz_pad = dyn.forward_backward(
        log_init, log_trans, ll_pad, mask)
    g_ref, xi_ref, lz_ref = dyn.forward_backward(
        log_init, log_trans, ll_obs, jnp.ones(T - P))

    assert np.isfinite(np.asarray(g_pad)).all()
    np.testing.assert_allclose(float(lz_pad), float(lz_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_pad[P:]), np.asarray(g_ref),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(xi_pad), np.asarray(xi_ref),
                               atol=1e-5)
    assert float(np.abs(np.asarray(g_pad[:P])).sum()) == 0.0


def test_forward_backward_fully_masked():
    S, T = 2, 5
    li = jnp.log(jnp.full((S,), 0.5))
    lt = jnp.log(jnp.full((S, S), 0.5))
    g, xi, lz = dyn.forward_backward(
        li, lt, jnp.full((T, S), jnp.nan), jnp.zeros(T))
    assert float(lz) == 0.0
    assert float(np.abs(np.asarray(g)).sum()) == 0.0
    assert float(np.abs(np.asarray(xi)).sum()) == 0.0


def test_factored_frontier_mask():
    """Masked steps hold the belief and contribute 0 to the loglik bound;
    the padded loglik values (NaN here) are never read."""
    rng = np.random.default_rng(1)
    T, C, S = 7, 2, 3
    init = jnp.asarray(rng.dirichlet(np.ones(S), size=C), jnp.float32)
    trans = jnp.asarray(rng.dirichlet(np.ones(S), size=(C, S)), jnp.float32)
    model = Factorial2TBN(init=init, trans=trans)
    ll = rng.standard_normal((T, C, S)).astype(np.float32)
    ll[3] = np.nan
    mask = np.ones(T, np.float32)
    mask[3] = 0.0
    beliefs, lls = factored_frontier_filter(
        model, jnp.asarray(ll), jnp.asarray(mask))
    assert np.isfinite(np.asarray(beliefs)).all()
    np.testing.assert_allclose(np.asarray(beliefs[3]), np.asarray(beliefs[2]),
                               atol=1e-6)
    assert float(lls[3]) == 0.0
    gam = factored_frontier_smooth(model, jnp.asarray(ll), jnp.asarray(mask))
    assert np.isfinite(np.asarray(gam)).all()
    # no-mask call == explicit all-ones mask (backward compatibility)
    ll_ok = jnp.asarray(rng.standard_normal((T, C, S)), jnp.float32)
    b1, l1 = factored_frontier_filter(model, ll_ok)
    b2, l2 = factored_frontier_filter(model, ll_ok, jnp.ones(T))
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-6)


# ---------------------------------------------------------------------------
# fused-vs-unfused parity (one per dynamic model class)
# ---------------------------------------------------------------------------


def test_hmm_fused_unfused_parity():
    stream = syn.hmm_sequences(s=16, t=12, states=2, f=2, seed=3)[0]
    m1 = HiddenMarkovModel(stream.attributes, n_states=2, seed=0)
    m2 = HiddenMarkovModel(stream.attributes, n_states=2, seed=0)
    e1 = m1.update_model(stream, sweeps=8, tol=0.0, fused=True)
    e2 = m2.update_model(stream, sweeps=8, tol=0.0, fused=False)
    np.testing.assert_allclose(e1, e2, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(m1.posterior.emis.m),
                               np.asarray(m2.posterior.emis.m), atol=1e-3)
    np.testing.assert_allclose(np.asarray(m1.posterior.trans.alpha),
                               np.asarray(m2.posterior.trans.alpha),
                               rtol=1e-3)
    # metrics pytree reports every sweep active at tol=0
    assert int(np.asarray(m1.fit_metrics.active).sum()) == 8


def test_arhmm_fused_unfused_parity():
    stream = syn.hmm_sequences(s=12, t=10, states=2, f=2, seed=4)[0]
    m1 = AutoRegressiveHMM(stream.attributes, n_states=2, seed=0)
    m2 = AutoRegressiveHMM(stream.attributes, n_states=2, seed=0)
    e1 = m1.update_model(stream, sweeps=5, tol=0.0, fused=True)
    e2 = m2.update_model(stream, sweeps=5, tol=0.0, fused=False)
    np.testing.assert_allclose(e1, e2, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(m1.posterior.emis.m),
                               np.asarray(m2.posterior.emis.m), atol=1e-3)


def test_fhmm_fused_unfused_parity():
    stream = syn.hmm_sequences(s=12, t=10, states=2, f=3, seed=5)[0]
    m1 = FactorialHMMModel(stream.attributes, n_chains=2, n_states=2, seed=0)
    m2 = FactorialHMMModel(stream.attributes, n_chains=2, n_states=2, seed=0)
    e1 = m1.update_model(stream, sweeps=6, tol=0.0, fused=True)
    e2 = m2.update_model(stream, sweeps=6, tol=0.0, fused=False)
    np.testing.assert_allclose(e1, e2, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(m1.means), np.asarray(m2.means),
                               atol=1e-3)


def test_fhmm_backend_parity():
    """The Pallas suff-stats backend (interpret mode on the CPU) fits the
    same factorial HMM as the einsum backend from one seed."""
    stream = syn.hmm_sequences(s=16, t=8, states=3, f=2, seed=0)[0]
    fits = {}
    for backend in ("einsum", "pallas"):
        m = FactorialHMMModel(stream.attributes, n_chains=2, n_states=2,
                              seed=0)
        elbo = m.update_model(stream, sweeps=2, tol=0.0, backend=backend)
        fits[backend] = (elbo, np.asarray(m.means))
    np.testing.assert_allclose(fits["pallas"][0], fits["einsum"][0],
                               rtol=1e-4)
    np.testing.assert_allclose(fits["pallas"][1], fits["einsum"][1],
                               atol=1e-4)


def test_kalman_fused_unfused_parity():
    stream = syn.lds_sequences(s=12, t=15, dim_h=2, f=3, seed=6)[0]
    m1 = KalmanFilter(stream.attributes, n_hidden=2, seed=0)
    m2 = KalmanFilter(stream.attributes, n_hidden=2, seed=0)
    e1 = m1.update_model(stream, sweeps=6, tol=0.0, fused=True)
    e2 = m2.update_model(stream, sweeps=6, tol=0.0, fused=False)
    np.testing.assert_allclose(e1, e2, rtol=1e-4)
    for a, b in ((m1.A, m2.A), (m1.C, m2.C), (m1.q, m2.q), (m1.r, m2.r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3)


def test_slds_fused_unfused_parity():
    stream = syn.slds_stream(1, s=12, t=16, dim_h=2, f=3, seed=7)[0][0]
    m1 = SwitchingLDS(stream.attributes, n_states=2, n_hidden=2, seed=0)
    m2 = SwitchingLDS(stream.attributes, n_states=2, n_hidden=2, seed=0)
    e1 = m1.update_model(stream, sweeps=4, tol=0.0, fused=True)
    e2 = m2.update_model(stream, sweeps=4, tol=0.0, fused=False)
    np.testing.assert_allclose(e1, e2, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(m1.A), np.asarray(m2.A), atol=2e-3)
    np.testing.assert_allclose(np.asarray(m1.resp), np.asarray(m2.resp),
                               atol=1e-3)


# ---------------------------------------------------------------------------
# factorial HMM structured VB vs exact joint-chain inference
# ---------------------------------------------------------------------------


def test_fhmm_estep_matches_exact_joint():
    """C=2 chains, S=2 states: run ONLY the structured mean-field E-step
    (fixed parameters, iterated Jacobi sweeps) and compare the per-chain
    marginals against EXACT forward-backward on the equivalent joint HMM
    (S^C = 4 states, additive means).  With well-separated contributions
    the factored posterior must recover the exact marginals."""
    rng = np.random.default_rng(8)
    B, T, F, C, S = 6, 14, 3, 2, 2
    means = jnp.asarray(
        np.stack([
            np.stack([np.full(F, -3.0), np.full(F, 3.0)]),     # chain 0
            np.stack([np.full(F, -1.0), np.full(F, 1.0)]),     # chain 1
        ]), jnp.float32)                                       # [C, S, F]
    trans = np.stack([0.1 + 0.8 * np.eye(S)] * C).astype(np.float32)
    log_trans = jnp.log(jnp.asarray(trans))
    log_init = jnp.log(jnp.full((C, S), 0.5))
    noise = jnp.asarray(0.25)

    # sample from the true generative model
    xs = np.zeros((B, T, F), np.float32)
    for b in range(B):
        z = rng.integers(0, S, C)
        for t in range(T):
            for c in range(C):
                z[c] = rng.choice(S, p=trans[c, z[c]])
            mu = np.asarray(means)[np.arange(C), z].sum(0)
            xs[b, t] = mu + np.sqrt(0.25) * rng.standard_normal(F)
    xc = jnp.asarray(xs)
    mask = jnp.ones((B, T))

    # structured VB E-step only: iterate _fhmm_sweep with FIXED params
    gammas = jnp.full((B, T, C, S), 1.0 / S)
    for _ in range(25):
        _, _, gammas, _ = dyn._fhmm_sweep(
            means, log_trans, log_init, noise, gammas, xc, mask, "einsum")

    # exact joint oracle: 4-state HMM, joint transition = kron of chains
    joint_means = (means[0][:, None, :] + means[1][None, :, :]
                   ).reshape(S * S, F)                          # [4, F]
    joint_trans = jnp.asarray(np.kron(trans[0], trans[1]))
    joint_init = jnp.full((S * S,), 1.0 / (S * S))
    diff = xc[:, :, None, :] - joint_means[None, None]
    ll = (-(0.5 / float(noise)) * (diff ** 2).sum(-1)
          - 0.5 * F * np.log(2 * np.pi * float(noise)))         # [B,T,4]
    g_joint = jnp.stack([
        dyn.forward_backward(jnp.log(joint_init), jnp.log(joint_trans),
                             ll[b], mask[b])[0]
        for b in range(B)])                                     # [B,T,4]
    g_joint = g_joint.reshape(B, T, S, S)
    marg0 = np.asarray(g_joint.sum(-1))                         # chain 0
    marg1 = np.asarray(g_joint.sum(-2))                         # chain 1

    g = np.asarray(gammas)
    assert (g[:, :, 0].argmax(-1) == marg0.argmax(-1)).mean() > 0.95
    assert (g[:, :, 1].argmax(-1) == marg1.argmax(-1)).mean() > 0.9
    assert np.abs(g[:, :, 0] - marg0).max() < 0.15


# ---------------------------------------------------------------------------
# SLDS regime segmentation
# ---------------------------------------------------------------------------


def test_slds_two_regime_segmentation():
    """Sequences switch dynamics (rotation -> reverse rotation) at the
    midpoint; the learnt switch responsibilities must segment the two
    halves (up to label permutation)."""
    stream = syn.slds_stream(1, s=24, t=40, dim_h=2, f=4, seed=9)[0][0]
    m = SwitchingLDS(stream.attributes, n_states=2, n_hidden=2, seed=0)
    m.update_model(stream, sweeps=12, tol=0.0)
    dec = np.asarray(m.resp).argmax(-1)                 # [B, T]
    T = dec.shape[1]
    true = (np.arange(T) >= T // 2).astype(int)[None].repeat(dec.shape[0], 0)
    # skip the first steps of each half (filter burn-in after the switch)
    keep = np.ones(T, bool)
    keep[:4] = False
    keep[T // 2: T // 2 + 4] = False
    agree = (dec[:, keep] == true[:, keep]).mean()
    assert max(agree, 1.0 - agree) > 0.75


# ---------------------------------------------------------------------------
# streaming (Eq. 3) with drift detection
# ---------------------------------------------------------------------------


def test_seq_stream_fit_detects_regime_switch(tmp_path):
    batches, attrs, switch_at = syn.hmm_stream(
        n_batches=6, s=24, t=16, states=2, f=2, shift=8.0, seed=10)
    m = HiddenMarkovModel(attrs, n_states=2, seed=0)
    with _obs_to(tmp_path) as path:
        info = seq_stream_fit(m, batches, sweeps=6, tol=0.0,
                              drift_threshold=5.0)
        counts = obs.validate_obs_events(path)
    drifted = np.asarray(info["drifted"]).astype(bool)
    assert m.n_drifts >= 1
    assert drifted.any()
    # the first firing must be at or after the regime switch
    assert int(np.argmax(drifted)) >= switch_at
    assert not drifted[:switch_at].any()
    assert counts.get("stream_batch", 0) == len(batches)
    assert counts.get("drift", 0) == int(drifted.sum())
    # the refit recovers: posterior means live near the shifted regime
    sm = np.sort(m.state_means()[:, 0])
    assert sm.max() > 6.0


# ---------------------------------------------------------------------------
# compiled-program cache: same shapes => no retrace
# ---------------------------------------------------------------------------


def test_update_model_does_not_retrace_same_shapes():
    stream = syn.hmm_sequences(s=8, t=10, states=2, f=2, seed=11)[0]
    m1 = HiddenMarkovModel(stream.attributes, n_states=2, seed=0)
    m1.update_model(stream, sweeps=3, tol=0.0)
    before = dyn.trace_counts().get("hmm_fit", 0)
    assert before >= 1
    # second fit on the SAME model (Bayesian update) and a FRESH model of
    # identical shape both reuse the compiled program
    m1.update_model(stream, sweeps=3, tol=0.0)
    m2 = HiddenMarkovModel(stream.attributes, n_states=2, seed=1)
    m2.update_model(stream, sweeps=3, tol=0.0)
    assert dyn.trace_counts().get("hmm_fit", 0) == before
    # a different shape DOES compile a new program (the cache key works)
    stream2 = syn.hmm_sequences(s=8, t=11, states=2, f=2, seed=11)[0]
    m3 = HiddenMarkovModel(stream2.attributes, n_states=2, seed=0)
    m3.update_model(stream2, sweeps=3, tol=0.0)
    assert dyn.trace_counts().get("hmm_fit", 0) == before + 1


# ---------------------------------------------------------------------------
# temporal serving through the query engine
# ---------------------------------------------------------------------------


def test_temporal_query_engine(tmp_path):
    stream = syn.hmm_sequences(s=16, t=12, states=3, f=2, seed=12)[0]
    m = HiddenMarkovModel(stream.attributes, n_states=3, seed=0)
    m.update_model(stream, sweeps=5)
    xc = np.asarray(stream.xc)

    with _obs_to(tmp_path) as path:
        eng = PGMQueryEngine(m, mode="temporal")
        qf = [eng.submit("filter", {}, payload=xc[i]) for i in range(3)]
        qp = eng.submit("predict", {"horizon": 4}, payload=xc[3])
        eng.flush()
        # same (T, horizon, cap) bucket again => compiled-program cache hit
        q2 = [eng.submit("filter", {}, payload=xc[i]) for i in range(4, 7)]
        eng.flush()
        counts = obs.validate_obs_events(path)
        events = [json.loads(l) for l in open(path)]

    for q in qf + q2:
        r = np.asarray(q.result)
        assert r.shape == (12, 3)
        np.testing.assert_allclose(r.sum(-1), 1.0, atol=1e-4)
    rp = np.asarray(qp.result)
    assert rp.shape == (3,)
    np.testing.assert_allclose(rp.sum(), 1.0, atol=1e-4)
    # parity with the model's own filtering API
    ref = np.asarray(m.filtered_posterior(jnp.asarray(xc[:3])))
    np.testing.assert_allclose(np.asarray(qf[0].result), ref[0], atol=1e-5)

    assert counts.get("temporal_plan", 0) == 2      # (T,0) and (T,4) buckets
    buckets = [e for e in events if e["event"] == "serve_bucket"]
    hits = [e["cache_hit"] for e in buckets]
    assert hits.count(True) == 1                    # the repeated filter bucket

    # invalid submissions are rejected up front
    with pytest.raises(ValueError):
        eng.submit("filter", {})                    # no payload
    with pytest.raises(ValueError):
        eng.submit("marginal", {}, payload=xc[0])   # unknown target


def test_temporal_engine_requires_temporal_model():
    stream = syn.lds_sequences(s=4, t=6, dim_h=2, f=2, seed=13)[0]
    kf = KalmanFilter(stream.attributes, n_hidden=2)
    with pytest.raises(ValueError):
        PGMQueryEngine(kf, mode="temporal")
