"""Streaming VB (Eq. 3), drift detection, SVI."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import streaming, svi, vmp
from repro.core.dag import PlateSpec
from repro.data.synthetic import drift_stream, gmm_stream


def _setup(f=3, k=2, seed=0):
    spec = PlateSpec(n_features=f, latent_card=k)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(seed))
    return cp, prior, init


def test_streaming_matches_batch_on_stationary_data():
    stream, means, _ = gmm_stream(1600, 2, 3, seed=7)
    cp, prior, init = _setup()
    # batch fit
    full = stream.collect()
    st = vmp.vmp_fit(cp, prior, init, full.xc, full.xd, 100, 1e-6)
    # streaming fit, 8 batches of 200
    ss = streaming.stream_init(prior, init)
    for b in stream.batches(200):
        ss, info = streaming.stream_update(cp, prior, ss, b.xc, b.xd)
    m_batch = np.sort(np.asarray(st.post.reg.m[:, :, 0]).ravel())
    m_stream = np.sort(np.asarray(ss.post.reg.m[:, :, 0]).ravel())
    np.testing.assert_allclose(m_stream, m_batch, atol=0.2)
    assert int(ss.n_drifts) == 0


def test_drift_detection_fires_on_shift():
    stream, n_phase = drift_stream(1500, 3, seed=8)
    cp, prior, init = _setup(k=1)
    ss = streaming.stream_init(prior, init)
    drift_batches = []
    for i, b in enumerate(stream.batches(250)):
        ss, info = streaming.stream_update(cp, prior, ss, b.xc, b.xd,
                                           drift_threshold=3.0)
        if bool(info["drifted"]):
            drift_batches.append(i)
    # phase flips at batch 6 (1500/250); drift must fire shortly after
    assert drift_batches, "no drift detected"
    assert min(drift_batches) in (6, 7), drift_batches
    # and the model must have re-adapted to the new mean (+6 shift)
    final_means = np.asarray(ss.post.reg.m[:, 0, 0])
    assert (final_means > 2.0).all(), final_means


def test_svi_converges_to_batch_posterior():
    stream, means, _ = gmm_stream(2000, 2, 3, seed=9)
    cp, prior, init = _setup(seed=1)
    full = stream.collect()
    st = vmp.vmp_fit(cp, prior, init, full.xc, full.xd, 100, 1e-6)
    state = svi.svi_init(init)
    for epoch in range(6):
        for b in stream.batches(250):
            state = svi.svi_step(cp, prior, state, b.xc, b.xd, 2000.0)
    post = svi.svi_posterior(state)
    m_b = np.sort(np.asarray(st.post.reg.m[:, :, 0]).ravel())
    m_s = np.sort(np.asarray(post.reg.m[:, :, 0]).ravel())
    np.testing.assert_allclose(m_s, m_b, atol=0.25)


def test_natural_coordinate_roundtrip():
    cp, prior, init = _setup()
    nat = svi.to_natural(init)
    back = svi.from_natural(nat)
    for a, b in zip(jax.tree_util.tree_leaves(init),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# -- Model.update_model streaming wiring (stream_fit underneath) --------------


def test_update_model_multibatch_stream_uses_stream_fit():
    """A multi-chunk DataStream routes through the resident stream_fit scan
    and matches the explicit per-batch stream_update loop."""
    from repro.data.stream import DataStream
    from repro.pgm_models import GaussianMixture

    full, _, _ = gmm_stream(1200, 2, 3, seed=11)
    batch = full.collect()
    xc = np.asarray(batch.xc)
    parts = [DataStream.from_arrays(full.attributes, xc[i:i + 300])
             for i in range(0, 1200, 300)]
    multi = DataStream.concat(parts)          # source yields 4 equal chunks

    m = GaussianMixture(full.attributes, n_states=2, seed=0)
    e = m.update_model(multi, sweeps=8)
    assert np.isfinite(e)
    assert m.n_seen == 1200

    # reference: the explicit per-batch streaming loop (same step body)
    ref = GaussianMixture(full.attributes, n_states=2, seed=0)
    ss = streaming.stream_init(ref._chained_prior, ref.posterior)
    for i in range(0, 1200, 300):
        ss, info = streaming.stream_update(
            ref.cp, ref.prior, ss, jnp.asarray(xc[i:i + 300]),
            jnp.zeros((300, 0), jnp.int32), sweeps=8)
    np.testing.assert_allclose(np.asarray(m.posterior.reg.m),
                               np.asarray(ss.post.reg.m), atol=2e-3)
    np.testing.assert_allclose(e, float(info["elbo"]), atol=2.0)


def test_update_model_stream_window_matches_full_scan():
    """stream_window= replays the same stream in device-sliced windows and
    lands on the same posterior as the whole-stream-resident scan."""
    from repro.data.stream import DataStream
    from repro.pgm_models import GaussianMixture

    full, _, _ = gmm_stream(1200, 2, 3, seed=12)
    batch = full.collect()
    xc = np.asarray(batch.xc)
    parts = [DataStream.from_arrays(full.attributes, xc[i:i + 300])
             for i in range(0, 1200, 300)]

    m = GaussianMixture(full.attributes, n_states=2, seed=0)
    e = m.update_model(DataStream.concat(parts), sweeps=8)
    mw = GaussianMixture(full.attributes, n_states=2, seed=0)
    ew = mw.update_model(DataStream.concat(parts), sweeps=8, stream_window=2)
    assert m.last_update.launches == 1 and mw.last_update.launches == 2
    np.testing.assert_allclose(np.asarray(m.posterior.reg.m),
                               np.asarray(mw.posterior.reg.m),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(e, ew, atol=1e-3)
    assert mw.n_seen == 1200


def test_update_model_ragged_stream_falls_back_to_per_batch():
    from repro.data.stream import DataStream
    from repro.pgm_models import GaussianMixture

    full, _, _ = gmm_stream(900, 2, 3, seed=12)
    xc = np.asarray(full.collect().xc)
    parts = [DataStream.from_arrays(full.attributes, xc[:500]),
             DataStream.from_arrays(full.attributes, xc[500:])]  # 500 + 400
    multi = DataStream.concat(parts)
    m = GaussianMixture(full.attributes, n_states=2, seed=0)
    e = m.update_model(multi, sweeps=8)
    assert np.isfinite(e)
    assert m.n_seen == 900
    assert m.last_update.launches == 2            # one vmp_fit a chunk


def test_update_model_single_chunk_stream_keeps_batch_path():
    """from_arrays streams yield one chunk -> the one-shot VMP fit."""
    from repro.pgm_models import GaussianMixture

    s, _, _ = gmm_stream(600, 2, 3, seed=13)
    m = GaussianMixture(s.attributes, n_states=2, seed=0)
    e = m.update_model(s, sweeps=30)
    assert np.isfinite(e)
    assert m.n_seen == 600


# -- one streaming update_model call, one device program ----------------------


def _mixed_chunks(n_chunks=8, rows=128, switch=4, seed=21):
    """Chunks of a mixed plate (3 Gaussian and 2 discrete leaves of
    cardinality 3, 2 latent classes) whose concept switches at chunk
    ``switch``: the means move by 6 and the discrete tables are redrawn."""
    from repro.data.stream import Attribute, FINITE, REAL

    rng = np.random.default_rng(seed)
    attrs = ([Attribute(f"G{i}", REAL) for i in range(3)]
             + [Attribute(f"D{i}", FINITE, 3) for i in range(2)])
    means = rng.uniform(-3, 3, (2, 3))
    tables = [rng.dirichlet(np.full(3, 0.5), (2, 2)) for _ in range(2)]
    chunks = []
    for t in range(n_chunks):
        c = int(t >= switch)
        z = rng.integers(0, 2, rows)
        xc = means[z] + 6.0 * c + 0.8 * rng.standard_normal((rows, 3))
        u = rng.random((rows, 2, 1))
        xd = (u > np.cumsum(tables[c][z], -1)).sum(-1)
        chunks.append((xc.astype(np.float32), xd.astype(np.int32)))
    return attrs, chunks


def _stream_of(attrs, chunks):
    from repro.data.stream import DataStream

    return DataStream(attrs, lambda: iter(chunks),
                      n_instances=sum(len(xc) for xc, _ in chunks))


def test_stream_update_model_runs_no_eager_op():
    """After warm-up, a streaming update_model call on equal chunks of a
    mixed plate dispatches no eager primitive: stack, state set-up and
    read-out all run inside its one program."""
    import sys

    import jax._src.dispatch as dispatch
    from repro.pgm_models.static import NaiveBayes

    attrs, chunks = _mixed_chunks()
    model = NaiveBayes(attrs, n_states=2, seed=0)
    for _ in range(2):                            # compile, then warm
        model.update_model(_stream_of(attrs, chunks), sweeps=10, tol=1e-5)
    code = dispatch.apply_primitive.__code__
    prims = []

    def hook(frame, event, arg):
        if event == "call" and frame.f_code is code:
            prims.append(frame.f_locals.get("prim"))

    sys.setprofile(hook)
    try:
        e = model.update_model(_stream_of(attrs, chunks), sweeps=10,
                               tol=1e-5)
    finally:
        sys.setprofile(None)
    assert prims == []
    assert model.last_update.launches == 1
    assert np.isfinite(e) and model.n_seen == 3 * 8 * 128
    assert np.asarray(model.last_update.sweeps).shape == (8,)


def test_stream_call_matches_stream_fit():
    """The fused update_model program equals stream_fit from stream_init
    on the same chunks, across a concept switch that fires the drift
    gate."""
    from repro.pgm_models.static import NaiveBayes

    attrs, chunks = _mixed_chunks()
    model = NaiveBayes(attrs, n_states=2, seed=0)
    xcs = tuple(jnp.asarray(xc) for xc, _ in chunks)
    xds = tuple(jnp.asarray(xd) for _, xd in chunks)
    kw = dict(sweeps=20, tol=1e-5, drift_threshold=3.0,
              backend=model.backend, chunk=model.chunk)
    post, info, passes, elbo, n = streaming._stream_call(
        model.cp, model.prior, model._chained_prior, model.posterior,
        xcs, xds, **kw)
    state, ref = streaming.stream_fit(
        model.cp, model.prior,
        streaming.stream_init(model._chained_prior, model.posterior),
        jnp.stack(xcs), jnp.stack(xds), **kw)
    drifted = np.asarray(ref["drifted"])
    assert drifted[4:].any() and not drifted[:4].any()
    for k in ("sweeps", "drifted", "quarantined"):
        np.testing.assert_array_equal(np.asarray(info[k]),
                                      np.asarray(ref[k]), err_msg=k)
    np.testing.assert_array_equal(np.asarray(passes),
                                  np.asarray(ref["sweeps"]) + 1)
    for a, b in zip(jax.tree_util.tree_leaves(post),
                    jax.tree_util.tree_leaves(state.post)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(info["elbo"]),
                               np.asarray(ref["elbo"]), rtol=1e-6)
    np.testing.assert_allclose(float(elbo), float(ref["elbo"][-1]),
                               rtol=1e-6)
    np.testing.assert_allclose(float(n), float(state.n_seen), rtol=1e-6)
