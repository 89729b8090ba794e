"""Fused streaming hot path: suff-stats backend parity, chunked local step,
stream_fit scan driver vs the per-batch loop, dvmp program caching."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import expfam as ef
from repro.core import streaming, vmp
from repro.core.dag import PlateSpec
from repro.data.synthetic import drift_stream, gmm_stream, nb_stream


def _mixed_setup(n=600, seed=0):
    """Mixed CLG + discrete plate with a masked tail (padded instances)."""
    spec = PlateSpec(n_features=5, latent_card=3,
                     discrete_features=((3, 3), (4, 2)))
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    post = vmp.symmetry_broken(prior, jax.random.PRNGKey(seed))
    xc = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, 3))
    xd = jax.random.randint(jax.random.PRNGKey(seed + 2), (n, 2), 0, 2)
    mask = jnp.concatenate([jnp.ones(n - n // 8), jnp.zeros(n // 8)])
    return cp, prior, post, xc, xd, mask


def _assert_stats_close(a, b, label, atol=5e-4, rtol=1e-4):
    # densify: the einsum backend stores the latent-latent block lazily as
    # [K, L, L] while the fused pallas kernel emits the full matrix
    ra, rb = ef.reg_dense(a.reg), ef.reg_dense(b.reg)
    for la, lb, name in [
        (a.counts, b.counts, "counts"), (ra.sxx, rb.sxx, "sxx"),
        (ra.sxy, rb.sxy, "sxy"), (ra.syy, rb.syy, "syy"),
        (a.disc, b.disc, "disc"), (a.n, b.n, "n"),
        (a.local_elbo, b.local_elbo, "local_elbo"),
    ]:
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   atol=atol, rtol=rtol,
                                   err_msg=f"{label}: {name}")


@pytest.mark.parametrize("backend", ["einsum", "pallas"])
@pytest.mark.parametrize("chunk", [None, 256, 100])  # 100 -> ragged last chunk
def test_local_step_backend_parity_mixed_plate(backend, chunk):
    """Fused/chunked backends match the reference einsum path on mixed
    CLG+discrete plates including padded/masked tail instances."""
    cp, prior, post, xc, xd, mask = _mixed_setup()
    ref_stats, ref_r = vmp.local_step(cp, post, xc, xd, mask)
    stats, r = vmp.local_step(cp, post, xc, xd, mask,
                              backend=backend, chunk=chunk)
    _assert_stats_close(ref_stats, stats, f"{backend}/{chunk}")
    np.testing.assert_allclose(np.asarray(ref_r), np.asarray(r), atol=1e-5)


@pytest.mark.parametrize("backend", ["einsum", "pallas"])
@pytest.mark.parametrize("L,latent_card", [(1, 0), (2, 3), (8, 2)])
def test_local_step_parity_latent_dim(backend, L, latent_card):
    """FA/PPCA plates (L > 0): the fused component-major kernel and the
    lazy-latent-block einsum path match the unchunked reference under
    chunked accumulation, across latent dims and with padded/masked tails
    (300 % 128 != 0 also exercises the kernel's instance padding)."""
    spec = PlateSpec(n_features=4, latent_card=latent_card, latent_dim=L)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    post = vmp.symmetry_broken(prior, jax.random.PRNGKey(3))
    xc = jax.random.normal(jax.random.PRNGKey(4), (300, 4))
    xd = jnp.zeros((300, 0), jnp.int32)
    mask = jnp.concatenate([jnp.ones(260), jnp.zeros(40)])
    ref_stats, ref_r = vmp.local_step(cp, post, xc, xd, mask)
    stats, r = vmp.local_step(cp, post, xc, xd, mask,
                              backend=backend, chunk=128)
    # within 1e-4 (1 + |ref|) on every statistic
    _assert_stats_close(ref_stats, stats, f"{backend}/L{L}", atol=1e-4)
    np.testing.assert_allclose(np.asarray(ref_r), np.asarray(r), atol=1e-5)


def test_local_step_latent_lazy_vs_fused_forms():
    """The einsum backend stores the leaf-shared latent-latent block ONCE
    ([K, L, L], no per-leaf broadcast); the fused pallas kernel emits the
    dense matrix; reg_dense reconciles them exactly."""
    spec = PlateSpec(n_features=5, latent_card=3, latent_dim=4)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    post = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    xc = jax.random.normal(jax.random.PRNGKey(1), (200, 5))
    xd = jnp.zeros((200, 0), jnp.int32)
    se, _ = vmp.local_step(cp, post, xc, xd, jnp.ones(200))
    sp, _ = vmp.local_step(cp, post, xc, xd, jnp.ones(200),
                           backend="pallas")
    lay = cp.layout
    assert se.reg.sxx_hh is not None
    assert se.reg.sxx_hh.shape == (lay.K, lay.L, lay.L)
    assert se.reg.sxx.shape == (lay.F, lay.K, 1 + lay.P, lay.D)
    assert sp.reg.sxx_hh is None
    assert sp.reg.sxx.shape == (lay.F, lay.K, lay.D, lay.D)
    dense = ef.reg_dense(se.reg)
    assert dense.sxx.shape == sp.reg.sxx.shape
    # the dense matrix is symmetric and its hh block is leaf-shared
    np.testing.assert_allclose(np.asarray(dense.sxx),
                               np.asarray(np.swapaxes(dense.sxx, -1, -2)),
                               atol=1e-6)
    # both feed the same conjugate update
    pe = vmp.global_update(prior, se)
    pp = vmp.global_update(prior, sp)
    np.testing.assert_allclose(np.asarray(pe.reg.m), np.asarray(pp.reg.m),
                               atol=1e-4)


def test_local_step_latent_nonuniform_mask_falls_back_dense():
    """Per-leaf latent masks (CustomGlobalLocalModel) keep the dense,
    leaf-dependent hh block on every backend — and they still agree."""
    spec = PlateSpec(n_features=3, latent_card=2, latent_dim=3)
    cp = vmp.compile_plate(spec, jnp.eye(3))
    post = vmp.symmetry_broken(vmp.default_prior(cp), jax.random.PRNGKey(2))
    xc = jax.random.normal(jax.random.PRNGKey(5), (150, 3))
    xd = jnp.zeros((150, 0), jnp.int32)
    se, _ = vmp.local_step(cp, post, xc, xd, jnp.ones(150))
    sp, _ = vmp.local_step(cp, post, xc, xd, jnp.ones(150),
                           backend="pallas")
    assert se.reg.sxx_hh is None and sp.reg.sxx_hh is None
    _assert_stats_close(se, sp, "nonuniform-mask")


def test_local_step_chunked_r_fixed():
    """Supervised path (clamped q(Z)) survives the chunked scan."""
    cp, prior, post, xc, xd, mask = _mixed_setup()
    rf = jax.nn.one_hot(
        jax.random.randint(jax.random.PRNGKey(9), (xc.shape[0],), 0, 3), 3)
    ref_stats, ref_r = vmp.local_step(cp, post, xc, xd, mask, rf)
    stats, r = vmp.local_step(cp, post, xc, xd, mask, rf,
                              backend="pallas", chunk=128)
    _assert_stats_close(ref_stats, stats, "r_fixed")
    np.testing.assert_allclose(np.asarray(ref_r), np.asarray(r), atol=1e-6)


def test_vmp_fit_backend_invariance():
    """Full fits agree across backends/chunking (same fixed point)."""
    stream, means, _ = gmm_stream(800, 2, 3, seed=5)
    full = stream.collect()
    spec = PlateSpec(n_features=3, latent_card=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    ref = vmp.vmp_fit(cp, prior, init, full.xc, full.xd, 60, 1e-6)
    st = vmp.vmp_fit(cp, prior, init, full.xc, full.xd, 60, 1e-6,
                     None, "pallas", 256)
    np.testing.assert_allclose(np.asarray(ref.post.reg.m),
                               np.asarray(st.post.reg.m), atol=1e-3)


# ---------------------------------------------------------------------------
# stream_fit scan driver vs the per-batch stream_update loop
# ---------------------------------------------------------------------------


def _stacked(batches):
    return (jnp.stack([b.xc for b in batches]),
            jnp.stack([b.xd for b in batches]),
            jnp.stack([b.mask for b in batches]))


def test_stream_fit_matches_loop_with_padded_tail():
    """Scan replay == per-batch loop on a stationary stream whose last
    batch is zero-padded and masked."""
    stream, _, _ = gmm_stream(1100, 2, 3, seed=7)   # 1100 % 250 != 0
    spec = PlateSpec(n_features=3, latent_card=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    batches = list(stream.batches(250))
    assert float(batches[-1].mask.sum()) < 250  # really exercises the pad

    ss = streaming.stream_init(prior, init)
    elbos = []
    for b in batches:
        ss, info = streaming.stream_update(cp, prior, ss, b.xc, b.xd,
                                           mask=b.mask)
        elbos.append(float(info["elbo"]))

    sf = streaming.stream_init(prior, init)
    sf, infos = streaming.stream_fit(cp, prior, sf, *_stacked(batches))

    np.testing.assert_allclose(np.asarray(ss.post.reg.m),
                               np.asarray(sf.post.reg.m),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(elbos), np.asarray(infos["elbo"]),
                               rtol=1e-4)
    assert float(ss.n_seen) == float(sf.n_seen) == 1100.0
    assert int(ss.n_drifts) == int(sf.n_drifts)


def test_stream_fit_drift_flags_match_loop():
    """Drift detection (flags, PH stats, n_drifts) is identical between the
    scan driver and the per-batch loop, and the model re-adapts."""
    stream, _ = drift_stream(1500, 3, seed=8)
    spec = PlateSpec(n_features=3, latent_card=1)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    batches = list(stream.batches(250))

    ss = streaming.stream_init(prior, init)
    loop_flags = []
    for b in batches:
        ss, info = streaming.stream_update(cp, prior, ss, b.xc, b.xd,
                                           drift_threshold=3.0)
        loop_flags.append(bool(info["drifted"]))

    sf = streaming.stream_init(prior, init)
    sf, infos = streaming.stream_fit(cp, prior, sf, *_stacked(batches),
                                     drift_threshold=3.0)
    scan_flags = [bool(d) for d in np.asarray(infos["drifted"])]

    assert loop_flags == scan_flags
    assert any(loop_flags), "drift never fired"
    assert int(ss.n_drifts) == int(sf.n_drifts) == sum(loop_flags)
    np.testing.assert_allclose(np.asarray(ss.post.reg.m),
                               np.asarray(sf.post.reg.m),
                               rtol=1e-4, atol=1e-4)
    # re-adapted to the +6 shifted phase
    assert (np.asarray(sf.post.reg.m[:, 0, 0]) > 2.0).all()


def test_stream_fit_pallas_backend_mixed_plate():
    """The fused backend drives the whole scan on a CLG+discrete stream."""
    stream, _ = nb_stream(240, 2, 2, 1, seed=3)
    batch = stream.collect()
    spec = PlateSpec(n_features=4, latent_card=2,
                     discrete_features=((2, 3), (3, 2)))
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(1))
    xcs = batch.xc.reshape(4, 60, 2)
    xds = batch.xd.reshape(4, 60, 2)

    ref, _ = streaming.stream_fit(cp, prior,
                                  streaming.stream_init(prior, init),
                                  xcs, xds, sweeps=3)
    got, infos = streaming.stream_fit(cp, prior,
                                      streaming.stream_init(prior, init),
                                      xcs, xds, sweeps=3,
                                      backend="pallas", chunk=32)
    np.testing.assert_allclose(np.asarray(ref.post.reg.m),
                               np.asarray(got.post.reg.m),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ref.post.disc.alpha),
                               np.asarray(got.post.disc.alpha),
                               rtol=1e-4, atol=1e-4)
    assert np.isfinite(np.asarray(infos["elbo"])).all()


def test_stream_fit_latent_plate_pallas_backend():
    """FA/PPCA plates (L > 0) ride the same donated single-scan streaming
    program as mixtures, on the fused kernel backend."""
    spec = PlateSpec(n_features=4, latent_card=2, latent_dim=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(1))
    xc = jax.random.normal(jax.random.PRNGKey(2), (240, 4))
    xcs = xc.reshape(4, 60, 4)
    xds = jnp.zeros((4, 60, 0), jnp.int32)

    ref, _ = streaming.stream_fit(cp, prior,
                                  streaming.stream_init(prior, init),
                                  xcs, xds, sweeps=3)
    got, infos = streaming.stream_fit(cp, prior,
                                      streaming.stream_init(prior, init),
                                      xcs, xds, sweeps=3,
                                      backend="pallas", chunk=32)
    np.testing.assert_allclose(np.asarray(ref.post.reg.m),
                               np.asarray(got.post.reg.m),
                               rtol=1e-4, atol=1e-4)
    assert np.isfinite(np.asarray(infos["elbo"])).all()


def test_dvmp_latent_plate_matches_single_device():
    """d-VMP psums the lazy latent-block message pytree correctly: the
    mesh fit equals the single-device fit on an FA-mixture plate."""
    from repro.core import dvmp
    from repro.launch.mesh import make_mesh

    spec = PlateSpec(n_features=3, latent_card=2, latent_dim=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    xc = jax.random.normal(jax.random.PRNGKey(3), (128, 3))
    xd = jnp.zeros((128, 0), jnp.int32)
    mesh = make_mesh((1,), ("data",))
    single = vmp.vmp_fit(cp, prior, init, xc, xd, 10, 0.0)
    dist = dvmp.dvmp_fit(cp, prior, init, xc, xd, mesh, ("data",), 10, 0.0)
    np.testing.assert_allclose(np.asarray(single.post.reg.m),
                               np.asarray(dist.post.reg.m),
                               rtol=1e-4, atol=1e-4)


def test_stream_fit_windowed_matches_full_scan():
    """window= replays the stream in device-sliced windows (host-resident
    stack) and matches the single full scan exactly, ragged tail included."""
    stream, _, _ = gmm_stream(1100, 2, 3, seed=7)
    spec = PlateSpec(n_features=3, latent_card=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    batches = list(stream.batches(250))
    xcs, xds, masks = _stacked(batches)
    xcs_h, xds_h, masks_h = (np.asarray(xcs), np.asarray(xds),
                             np.asarray(masks))

    ref, iref = streaming.stream_fit(cp, prior,
                                     streaming.stream_init(prior, init),
                                     xcs, xds, masks)
    win, iwin = streaming.stream_fit(cp, prior,
                                     streaming.stream_init(prior, init),
                                     xcs_h, xds_h, masks_h, window=2)
    np.testing.assert_allclose(np.asarray(ref.post.reg.m),
                               np.asarray(win.post.reg.m),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(iref["elbo"]),
                               np.asarray(iwin["elbo"]), rtol=1e-5)
    assert iwin["elbo"].shape[0] == len(batches)
    assert float(ref.n_seen) == float(win.n_seen) == 1100.0


def test_stream_fit_donation_keeps_inputs_alive():
    """stream_init copies the globals, so the caller's prior/init (and a
    second replay from the same arrays) survive buffer donation."""
    stream, _, _ = gmm_stream(400, 2, 3, seed=2)
    spec = PlateSpec(n_features=3, latent_card=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    batches = list(stream.batches(100))
    xcs, xds, masks = _stacked(batches)
    s1, _ = streaming.stream_fit(cp, prior,
                                 streaming.stream_init(prior, init),
                                 xcs, xds, masks)
    s2, _ = streaming.stream_fit(cp, prior,
                                 streaming.stream_init(prior, init),
                                 xcs, xds, masks)
    np.testing.assert_allclose(np.asarray(s1.post.reg.m),
                               np.asarray(s2.post.reg.m))
    assert np.isfinite(float(prior.mix.alpha.sum()))


# ---------------------------------------------------------------------------
# dvmp program caching (the per-batch retrace bug)
# ---------------------------------------------------------------------------


def test_dvmp_programs_are_cached():
    from repro.core import dvmp
    from repro.launch.mesh import make_mesh

    stream, _, _ = gmm_stream(64, 2, 3, seed=1)
    full = stream.collect()
    spec = PlateSpec(n_features=3, latent_card=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    mesh = make_mesh((1,), ("data",))
    mask = jnp.ones(64)

    dvmp._sweep_program.cache_clear()
    dvmp._fit_program.cache_clear()
    post, e = dvmp.dvmp_one_sweep(cp, prior, init, full.xc, full.xd, mask,
                                  mesh, ("data",))
    for _ in range(3):
        post, e = dvmp.dvmp_one_sweep(cp, prior, post, full.xc, full.xd,
                                      mask, mesh, ("data",))
    info = dvmp._sweep_program.cache_info()
    assert info.currsize == 1, "one program per (cp, mesh, axes)"
    assert info.hits == 3

    for _ in range(2):
        dvmp.dvmp_fit(cp, prior, init, full.xc, full.xd, mesh, ("data",),
                      10, 1e-4)
    assert dvmp._fit_program.cache_info().currsize == 1
    assert np.isfinite(float(e))


def test_posterior_z_is_jitted_and_correct():
    stream, _, labels = gmm_stream(900, 2, 3, seed=6)
    full = stream.collect()
    spec = PlateSpec(n_features=3, latent_card=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    st = vmp.vmp_fit(cp, prior, init, full.xc, full.xd, 80, 1e-6)
    r = vmp.posterior_z(cp, st.post, full.xc, full.xd)
    r_chunked = vmp.posterior_z(cp, st.post, full.xc, full.xd,
                                backend="pallas", chunk=256)
    np.testing.assert_allclose(np.asarray(r), np.asarray(r_chunked),
                               atol=1e-5)
    acc = max(float((np.asarray(r).argmax(1) == labels).mean()),
              float((np.asarray(r).argmax(1) != labels).mean()))
    assert acc > 0.95
