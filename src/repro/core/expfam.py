"""Conjugate exponential-family algebra in natural-parameter form.

This is the quantitative substrate of the toolbox (paper §2.1/§2.2): every
distribution is represented by a parameter pytree, and Bayesian updating
(paper Eq. 3) is *addition of expected sufficient statistics to natural
parameters*.  VMP, d-VMP, SVI and streaming VB all reduce to this algebra,
which is why one engine serves every model in the zoo (paper Table 2).

Families provided (all vectorized — leading axes broadcast):
  * Dirichlet         — conjugate prior of Multinomial/Categorical
  * NormalGamma       — conjugate prior of a univariate Gaussian (mean+precision)
  * MVNormalGamma     — conjugate prior of a linear-Gaussian node (CLG, Eq. 2):
                        regression weights w and noise precision lambda
  * Gaussian utils    — moments/KL for local continuous latents (FA, LDS)

Everything is pure-functional jnp; no Python objects cross jit boundaries.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax.numpy as jnp
from jax.scipy.special import digamma, gammaln

LOG2PI = math.log(2.0 * math.pi)

# ---------------------------------------------------------------------------
# Dirichlet / Categorical
# ---------------------------------------------------------------------------


class Dirichlet(NamedTuple):
    """Dirichlet in 'pseudo-count' parameterization; natural param = alpha - 1."""

    alpha: jnp.ndarray  # [..., K]


def dirichlet_expected_logprob(d: Dirichlet) -> jnp.ndarray:
    """E[log pi_k] under Dirichlet(alpha)."""
    return digamma(d.alpha) - digamma(d.alpha.sum(-1, keepdims=True))


def dirichlet_mean(d: Dirichlet) -> jnp.ndarray:
    return d.alpha / d.alpha.sum(-1, keepdims=True)


def dirichlet_logZ(d: Dirichlet) -> jnp.ndarray:
    return gammaln(d.alpha).sum(-1) - gammaln(d.alpha.sum(-1))


def dirichlet_kl(q: Dirichlet, p: Dirichlet) -> jnp.ndarray:
    """KL(q || p) for Dirichlets, summed over the last axis."""
    elp = dirichlet_expected_logprob(q)
    return (
        -dirichlet_logZ(q)
        + dirichlet_logZ(p)
        + ((q.alpha - p.alpha) * elp).sum(-1)
    )


def dirichlet_update(prior: Dirichlet, counts: jnp.ndarray) -> Dirichlet:
    """Conjugate update: posterior alpha = prior alpha + expected counts."""
    return Dirichlet(prior.alpha + counts)


# ---------------------------------------------------------------------------
# Normal-Gamma / univariate Gaussian (unknown mean and precision)
# ---------------------------------------------------------------------------


class NormalGamma(NamedTuple):
    """p(mu, lam) = N(mu | mu0, (kappa lam)^-1) Gamma(lam | a, b). Broadcasts."""

    mu0: jnp.ndarray
    kappa: jnp.ndarray
    a: jnp.ndarray
    b: jnp.ndarray


class GaussSuffStats(NamedTuple):
    """Weighted sufficient statistics of scalar observations.

    n = sum_i w_i, sx = sum_i w_i x_i, sx2 = sum_i w_i x_i^2.
    This triplet is THE message that d-VMP psums across data shards.
    """

    n: jnp.ndarray
    sx: jnp.ndarray
    sx2: jnp.ndarray


def gauss_suffstats(x: jnp.ndarray, w: jnp.ndarray) -> GaussSuffStats:
    """x: [N, ...], w: [N, ...] responsibilities; reduces over axis 0."""
    return GaussSuffStats(
        n=w.sum(0), sx=(w * x).sum(0), sx2=(w * x * x).sum(0)
    )


def normalgamma_update(prior: NormalGamma, s: GaussSuffStats) -> NormalGamma:
    """Standard conjugate Normal-Gamma update from weighted suff stats."""
    n = s.n
    kappa_n = prior.kappa + n
    mu_n = (prior.kappa * prior.mu0 + s.sx) / kappa_n
    a_n = prior.a + 0.5 * n
    # scatter around the weighted mean, guarded for n == 0
    xbar = s.sx / jnp.maximum(n, 1e-12)
    scatter = s.sx2 - n * xbar * xbar
    b_n = prior.b + 0.5 * (
        scatter
        + prior.kappa * n * (xbar - prior.mu0) ** 2 / kappa_n
    )
    return NormalGamma(mu_n, kappa_n, a_n, b_n)


class GaussMoments(NamedTuple):
    """Expected natural statistics of the Gaussian under a NormalGamma posterior."""

    e_lam: jnp.ndarray      # E[lambda]
    e_loglam: jnp.ndarray   # E[log lambda]
    e_lammu: jnp.ndarray    # E[lambda mu]
    e_lammu2: jnp.ndarray   # E[lambda mu^2]


def normalgamma_moments(q: NormalGamma) -> GaussMoments:
    e_lam = q.a / q.b
    return GaussMoments(
        e_lam=e_lam,
        e_loglam=digamma(q.a) - jnp.log(q.b),
        e_lammu=e_lam * q.mu0,
        e_lammu2=1.0 / q.kappa + e_lam * q.mu0 * q.mu0,
    )


def gauss_expected_loglik(x: jnp.ndarray, m: GaussMoments) -> jnp.ndarray:
    """E_q[log N(x | mu, lambda^-1)] — the VMP message from a Gaussian child."""
    return 0.5 * (
        m.e_loglam - LOG2PI - m.e_lam * x * x + 2.0 * x * m.e_lammu - m.e_lammu2
    )


def gamma_kl(a_q, b_q, a_p, b_p) -> jnp.ndarray:
    return (
        (a_q - a_p) * digamma(a_q)
        - gammaln(a_q)
        + gammaln(a_p)
        + a_p * (jnp.log(b_q) - jnp.log(b_p))
        + a_q * (b_p - b_q) / b_q
    )


def normalgamma_kl(q: NormalGamma, p: NormalGamma) -> jnp.ndarray:
    """KL(q || p) between Normal-Gamma distributions (elementwise)."""
    e_lam = q.a / q.b
    # E_q[ log N(mu | p.mu0, (p.kappa lam)^-1) - log N(mu | q.mu0, (q.kappa lam)^-1) ]
    kl_mu = 0.5 * (
        jnp.log(q.kappa / p.kappa)
        + p.kappa / q.kappa
        - 1.0
        + p.kappa * e_lam * (q.mu0 - p.mu0) ** 2
    )
    return kl_mu + gamma_kl(q.a, q.b, p.a, p.b)


# ---------------------------------------------------------------------------
# Multivariate Normal-Gamma — Bayesian linear regression / CLG node (Eq. 2)
# ---------------------------------------------------------------------------


class MVNormalGamma(NamedTuple):
    """p(w, lam) = N(w | m, (lam K)^-1) Gamma(lam | a, b); w in R^D.

    This is the conjugate parameter family of the paper's CLG node
    p(z | x_C) = N(z ; w^T [x_C, 1], lam^-1): the per-discrete-configuration
    regression of Eq. 2 (alpha/beta absorbed into w via a bias feature).
    Batched over leading axes of m/K/a/b (e.g. one regression per discrete
    parent configuration and per mixture component).
    """

    m: jnp.ndarray  # [..., D]
    K: jnp.ndarray  # [..., D, D]  (precision scale)
    a: jnp.ndarray  # [...]
    b: jnp.ndarray  # [...]


class RegSuffStats(NamedTuple):
    """Weighted regression suff stats: the d-VMP message of a CLG node.

    ``sxx_hh`` is the lazy latent-block form used by the FA/PPCA plates:
    when set, ``sxx`` carries only the top [..., Do, D] block (observed rows;
    the observed-latent cross block sits in its last L columns) and
    ``sxx_hh`` holds the leaf-shared [K, L, L] latent-latent block ONCE
    instead of broadcast per leaf.  :func:`reg_dense` reassembles the full
    symmetric [..., D, D] matrix; every consumer of ``sxx`` densifies first.
    """

    sxx: jnp.ndarray  # [..., D, D] sum w x x^T  ([..., Do, D] when lazy)
    sxy: jnp.ndarray  # [..., D]    sum w x y
    syy: jnp.ndarray  # [...]       sum w y^2
    n: jnp.ndarray    # [...]       sum w
    sxx_hh: Optional[jnp.ndarray] = None  # [K, L, L] shared latent block


def reg_dense(s: RegSuffStats) -> RegSuffStats:
    """Expand the lazy latent-block form to the full [..., D, D] sxx."""
    if s.sxx_hh is None:
        return s
    D = s.sxx.shape[-1]
    Do = s.sxx.shape[-2]
    L = D - Do
    oh = s.sxx[..., :, Do:]                               # [..., Do, L]
    hh = jnp.broadcast_to(s.sxx_hh, s.sxx.shape[:-2] + (L, L))
    bot = jnp.concatenate([jnp.swapaxes(oh, -1, -2), hh], axis=-1)
    return RegSuffStats(jnp.concatenate([s.sxx, bot], axis=-2),
                        s.sxy, s.syy, s.n, None)


def reg_suffstats(x: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray) -> RegSuffStats:
    """x: [N, D] features, y: [N] target, w: [N, ...] responsibilities.

    Returns stats with trailing batch axes matching w's trailing axes.
    """
    # einsum handles the general [N, ...] weight layout
    sxx = jnp.einsum("nd,ne,n...->...de", x, x, w)
    sxy = jnp.einsum("nd,n,n...->...d", x, y, w)
    syy = jnp.einsum("n,n,n...->...", y, y, w)
    n = w.sum(0)
    return RegSuffStats(sxx, sxy, syy, n)


def mvnormalgamma_update(prior: MVNormalGamma, s: RegSuffStats) -> MVNormalGamma:
    s = reg_dense(s)                     # lazy latent block expands HERE, once
    K_n = prior.K + s.sxx
    km = jnp.einsum("...de,...e->...d", prior.K, prior.m)
    rhs = km + s.sxy
    m_n = jnp.linalg.solve(K_n, rhs[..., None])[..., 0]
    a_n = prior.a + 0.5 * s.n
    quad_prior = jnp.einsum("...d,...d->...", prior.m, km)
    quad_post = jnp.einsum(
        "...d,...de,...e->...", m_n, K_n, m_n
    )
    b_n = prior.b + 0.5 * (s.syy + quad_prior - quad_post)
    # numerical guard: b must stay positive
    b_n = jnp.maximum(b_n, 1e-10)
    return MVNormalGamma(m_n, K_n, a_n, b_n)


class RegMoments(NamedTuple):
    e_lam: jnp.ndarray      # [...]
    e_loglam: jnp.ndarray   # [...]
    e_lamw: jnp.ndarray     # [..., D]     E[lam w]
    e_lamww: jnp.ndarray    # [..., D, D]  E[lam w w^T]


def mvnormalgamma_moments(q: MVNormalGamma) -> RegMoments:
    e_lam = q.a / q.b
    K_inv = jnp.linalg.inv(q.K)
    return RegMoments(
        e_lam=e_lam,
        e_loglam=digamma(q.a) - jnp.log(q.b),
        e_lamw=e_lam[..., None] * q.m,
        e_lamww=K_inv + e_lam[..., None, None] * (q.m[..., :, None] * q.m[..., None, :]),
    )


def reg_expected_loglik(x: jnp.ndarray, y: jnp.ndarray, m: RegMoments) -> jnp.ndarray:
    """E_q[log N(y | w^T x, lam^-1)] for x: [N, D], y: [N]; broadcasts moments."""
    quad = jnp.einsum("nd,...de,ne->n...", x, m.e_lamww, x)
    lin = jnp.einsum("nd,...d->n...", x, m.e_lamw)
    y_ = y.reshape(y.shape + (1,) * (quad.ndim - 1))
    return 0.5 * (
        m.e_loglam - LOG2PI - m.e_lam * y_ * y_ + 2.0 * y_ * lin - quad
    )


def mvnormalgamma_kl(q: MVNormalGamma, p: MVNormalGamma) -> jnp.ndarray:
    """KL(q || p) (elementwise over batch axes)."""
    D = q.m.shape[-1]
    e_lam = q.a / q.b
    Kq_inv = jnp.linalg.inv(q.K)
    dm = q.m - p.m
    _, logdet_q = jnp.linalg.slogdet(q.K)
    _, logdet_p = jnp.linalg.slogdet(p.K)
    tr = jnp.einsum("...de,...ed->...", p.K, Kq_inv)
    quad = e_lam * jnp.einsum("...d,...de,...e->...", dm, p.K, dm)
    kl_w = 0.5 * (logdet_q - logdet_p + tr + quad - D)
    return kl_w + gamma_kl(q.a, q.b, p.a, p.b)


# ---------------------------------------------------------------------------
# Gaussian helpers for local continuous latents (FA / Kalman smoothing)
# ---------------------------------------------------------------------------


def gaussian_kl_standard(mean: jnp.ndarray, cov: jnp.ndarray) -> jnp.ndarray:
    """KL( N(mean, cov) || N(0, I) ) with cov: [..., D, D]."""
    D = mean.shape[-1]
    _, logdet = jnp.linalg.slogdet(cov)
    tr = jnp.trace(cov, axis1=-2, axis2=-1)
    return 0.5 * (tr + (mean * mean).sum(-1) - D - logdet)


def categorical_entropy(logp: jnp.ndarray) -> jnp.ndarray:
    """Entropy of categorical given normalized log-probs [..., K]."""
    p = jnp.exp(logp)
    return -(p * logp).sum(-1)
