"""VMP engine: recovery, ELBO monotonicity, inference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import vmp
from repro.core.dag import PlateSpec


@pytest.fixture(scope="module")
def gmm_data():
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    N = 1500
    z = jax.random.bernoulli(k1, 0.4, (N,)).astype(int)
    mus = jnp.array([[3.0, -2.0, 0.0], [-3.0, 2.0, 5.0]])
    x = mus[z] + 0.7 * jax.random.normal(k2, (N, 3))
    return x, z, mus, k3


def test_gmm_recovery(gmm_data):
    x, z, mus, key = gmm_data
    spec = PlateSpec(n_features=3, latent_card=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, key)
    xd = jnp.zeros((x.shape[0], 0), jnp.int32)
    st = vmp.vmp_fit(cp, prior, init, x, xd, 100, 1e-6)
    learnt = np.sort(np.asarray(st.post.reg.m[:, :, 0]).T, axis=0)
    np.testing.assert_allclose(learnt, np.sort(np.asarray(mus), 0), atol=0.15)
    # perfect clustering up to label swap
    r = vmp.posterior_z(cp, st.post, x, xd)
    acc = max(float((r.argmax(1) == z).mean()),
              float((r.argmax(1) != z).mean()))
    assert acc > 0.98


def test_elbo_increases_over_sweeps(gmm_data):
    x, _, _, key = gmm_data
    spec = PlateSpec(n_features=3, latent_card=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    post = vmp.symmetry_broken(prior, key)
    xd = jnp.zeros((x.shape[0], 0), jnp.int32)
    mask = jnp.ones(x.shape[0])
    elbos = []
    for _ in range(8):
        stats, _ = vmp.local_step(cp, post, x, xd, mask)
        post = vmp.global_update(prior, stats)
        elbos.append(float(vmp.elbo(cp, prior, post, stats)))
    diffs = np.diff(elbos)
    assert (diffs > -1e-3 * np.abs(np.asarray(elbos[1:]))).all(), elbos


def test_supervised_r_fixed(gmm_data):
    """Clamping q(Z) to the labels gives class-conditional estimates."""
    x, z, mus, key = gmm_data
    spec = PlateSpec(n_features=3, latent_card=2)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    xd = jnp.zeros((x.shape[0], 0), jnp.int32)
    r = jax.nn.one_hot(z, 2)
    stats, _ = vmp.local_step(cp, prior, x, xd, jnp.ones(x.shape[0]), r)
    post = vmp.global_update(prior, stats)
    learnt = np.asarray(post.reg.m[:, :, 0]).T   # [K, F]
    np.testing.assert_allclose(learnt, np.asarray(mus), atol=0.15)


def test_latent_dim_fa_structure():
    """PPCA-style plate: latent H explains cross-feature covariance."""
    key = jax.random.PRNGKey(3)
    k1, k2, k3 = jax.random.split(key, 3)
    N, F, L = 1200, 5, 2
    W = jax.random.normal(k1, (F, L))
    h = jax.random.normal(k2, (N, L))
    x = h @ W.T + 0.2 * jax.random.normal(k3, (N, F))
    spec = PlateSpec(n_features=F, latent_card=0, latent_dim=L)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, key)
    st = vmp.vmp_fit(cp, prior, init, x, jnp.zeros((N, 0), jnp.int32),
                     120, 1e-6)
    lay = cp.layout
    loadings = np.asarray(st.post.reg.m[:, 0, 1 + lay.P:])   # [F, L]
    u1, _, _ = np.linalg.svd(np.asarray(W), full_matrices=False)
    u2, _, _ = np.linalg.svd(loadings, full_matrices=False)
    # principal angle overlap of the column spaces
    s = np.linalg.svd(u1.T @ u2)[1]
    assert s.min() > 0.9, s


def _mixed_plate(n, seed):
    from repro.data.synthetic import nb_stream

    stream, _ = nb_stream(n, 3, 2, 2, seed=seed)
    batch = stream.collect()   # xd: 2 discrete features + the class column
    spec = PlateSpec(n_features=5, latent_card=3,
                     discrete_features=((2, 3), (3, 3), (4, 3)))
    return spec, batch


def test_mixed_discrete_continuous():
    spec, batch = _mixed_plate(1200, seed=4)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(1))
    st = vmp.vmp_fit(cp, prior, init, batch.xc, batch.xd, 80, 1e-6)
    assert np.isfinite(float(st.elbo))


def _gather_loglik(e_logtheta, xd):
    """The discrete-leaf term as a gather: the look-up it replaced."""
    return jnp.take_along_axis(
        jnp.transpose(e_logtheta, (0, 2, 1))[None],          # [1, Fd, C, K]
        xd.astype(jnp.int32)[..., None, None],               # [N, Fd, 1, 1]
        axis=2,
    )[..., 0, :].sum(1)                                      # [N, K]


def _check_table(Fd, K, C):
    k1, k2 = jax.random.split(jax.random.PRNGKey(Fd * 1000 + K * 100 + C))
    table = 10.0 * jax.random.normal(k1, (Fd, K, C))
    table = table.at[0, 0, 0].set(-0.0)
    xd = jax.random.randint(k2, (2048, Fd), 0, C)
    got = jax.jit(vmp._disc_loglik)(table, xd)
    assert got.shape == (2048, K)
    np.testing.assert_array_equal(got, jax.jit(_gather_loglik)(table, xd))


def _check_out_of_range():
    Fd, K, C = 2, 3, 4
    table = jax.random.normal(jax.random.PRNGKey(0), (Fd, K, C))
    xd = jnp.array([[0, 3], [-1, 2], [-C, 1], [C, 0], [1, -C - 1],
                    [C + 5, -C - 7]], jnp.int32)
    got = np.asarray(jax.jit(vmp._disc_loglik)(table, xd))
    np.testing.assert_array_equal(got, jax.jit(_gather_loglik)(table, xd))
    # -1 wraps to C-1 and -C to 0; C and -C-1 read NaN in every class
    np.testing.assert_array_equal(got[1], table[0, :, C - 1] + table[1, :, 2])
    np.testing.assert_array_equal(got[2], table[0, :, 0] + table[1, :, 1])
    assert np.isfinite(got[:3]).all() and np.isnan(got[3:]).all()

    # a batch holding an out-of-range category is quarantined: the stream
    # ends where one that never saw the batch ends
    from repro.core import streaming

    spec, batch = _mixed_plate(4 * 150, seed=5)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(2))
    xcs = batch.xc.reshape(4, 150, -1)
    xds = batch.xd.reshape(4, 150, -1)
    bad = xds.at[2, 7, 1].set(3)   # cardinality 3: category 3 is out of range
    sp, info = streaming.stream_fit(cp, prior,
                                    streaming.stream_init(prior, init),
                                    xcs, bad)
    keep = np.array([0, 1, 3])
    sc, _ = streaming.stream_fit(cp, prior,
                                 streaming.stream_init(prior, init),
                                 xcs[keep], xds[keep])
    assert list(np.asarray(info["quarantined"]).astype(bool)) == [
        False, False, True, False]
    assert int(sp.n_quarantined) == 1
    for a, b in zip(jax.tree_util.tree_leaves(sp.post),
                    jax.tree_util.tree_leaves(sc.post)):
        np.testing.assert_array_equal(a, b)


def _check_fit(monkeypatch):
    spec, batch = _mixed_plate(1200, seed=4)

    def fit():
        # a plate of its own per path: the jitted fit is keyed on it
        cp = vmp.compile_plate(spec)
        prior = vmp.default_prior(cp)
        init = vmp.symmetry_broken(prior, jax.random.PRNGKey(1))
        st = vmp.vmp_fit(cp, prior, init, batch.xc, batch.xd, 30, 1e-6)
        stats, r = vmp.local_step(cp, st.post, batch.xc, batch.xd,
                                  jnp.ones(batch.xc.shape[0]))
        return st, stats, r

    got = fit()
    with monkeypatch.context() as m:
        m.setattr(vmp, "_disc_loglik", _gather_loglik)
        want = fit()
    assert int(got[0].sweep) == int(want[0].sweep)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", [
    (2, 3, 4),      # the nb_mixed leaves: 2 of cardinality 4, 3 classes
    (1, 2, 2),      # a single leaf
    (3, 4, 1),      # C = 1: every index is 0
    (5, 4, 7),
    (2, 3, 64),
    "out_of_range",
    "fit",
])
def test_disc_lookup_matches_gather(case, monkeypatch):
    """The discrete-leaf term of the local step is a compare-select chain;
    it gives the numbers of ``take_along_axis`` bit for bit, out-of-range
    categories included, and a whole fit is unchanged by it."""
    if case == "out_of_range":
        _check_out_of_range()
    elif case == "fit":
        _check_fit(monkeypatch)
    else:
        _check_table(*case)
