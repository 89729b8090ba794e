"""Plain reference: variational message passing for a latent-class plate.

The model of ``gmm_large`` and ``nb_mixed``: a class ``Z ~ Cat(pi)`` per
instance, Gaussian leaves ``X_f | Z=k ~ N(mu_fk, 1/lam_fk)`` with a
Normal-Gamma prior per (leaf, class), discrete leaves ``X_d | Z=k ~
Cat(theta_dk)`` with Dirichlet priors, and a Dirichlet prior on ``pi``.
Priors are the toolbox's defaults (all Dirichlet pseudo-counts 1; mean 0,
precision scale 1, Gamma(1, 1)).  The initial posterior jitters the means
by ``0.5 * N(0, 1)`` and the discrete pseudo-counts by ``exp(0.1 * N(0,
1))`` from ``PRNGKey(model_seed)``, as the toolbox does.

Written from the equations, for one design column (no observed parents,
no continuous latent), in straightforward ``jax.numpy``: every reduction is
a plain sum over instances at ``highest`` matmul precision, and ``dtype``
sets the precision of every array, so the same code run in ``bfloat16`` is
the benchmark's control.  It imports nothing of the program.

Parameters are dicts: ``mix [K]``, ``m, kk, a, b [F, K]`` (mean, precision
scale, Gamma shape and rate) and ``disc [Fd, K, M]``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import digamma, gammaln

LOG2PI = math.log(2.0 * math.pi)
HI = jax.lax.Precision.HIGHEST


def _sizes(cfg):
    cards = cfg["discrete_cards"]
    return (cfg["latent_card"], cfg["continuous"], len(cards),
            max(cards, default=2))


def prior(cfg, dtype=jnp.float32):
    K, F, Fd, M = _sizes(cfg)
    live = np.zeros((max(Fd, 1), M), np.float32)
    for d, c in enumerate(cfg["discrete_cards"]):
        live[d, :c] = 1.0
    p = dict(mix=np.ones(K), m=np.zeros((F, K)), kk=np.ones((F, K)),
             a=np.ones((F, K)), b=np.ones((F, K)),
             disc=live[:, None, :] * np.ones((1, K, 1)) + 1e-12)
    return {k: jnp.asarray(v, jnp.float32).astype(dtype) for k, v in p.items()}


def initial(pr, model_seed: int, dtype=jnp.float32):
    """The symmetry-broken starting posterior."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(model_seed))
    F, K = pr["m"].shape
    m = 0.5 * jax.random.normal(k1, (F, K, 1))[..., 0]
    disc = pr["disc"].astype(jnp.float32) * jnp.exp(
        0.1 * jax.random.normal(k2, pr["disc"].shape))
    return dict(pr, m=(pr["m"].astype(jnp.float32) + m).astype(dtype),
                disc=disc.astype(dtype))


def _elogdir(alpha):
    return digamma(alpha) - digamma(alpha.sum(-1, keepdims=True))


def _logits(p, xc, xd):
    """``[N, K]`` E_q[log p(z=k, x_n)] up to the per-instance constant."""
    e_lam = p["a"] / p["b"]
    e_loglam = digamma(p["a"]) - jnp.log(p["b"])
    e_lamw = e_lam * p["m"]
    e_lamww = 1.0 / p["kk"] + e_lam * p["m"] * p["m"]
    y = xc[:, :, None]                                          # [N, F, 1]
    ll = 0.5 * (e_loglam - LOG2PI - e_lam * y * y + 2.0 * e_lamw * y
                - e_lamww)                                      # [N, F, K]
    out = _elogdir(p["mix"])[None] + ll.sum(1)
    if xd.shape[1]:
        el = _elogdir(p["disc"])                                # [Fd, K, M]
        for d in range(xd.shape[1]):
            out = out + el[d].T[xd[:, d]]
    return out


@jax.jit
def qz(p, xc, xd):
    """q(Z | x) of each row under the posterior ``p``."""
    dtype = p["m"].dtype
    return jax.nn.softmax(_logits(p, xc.astype(dtype), xd), axis=-1)


@jax.jit
def local(p, xc, xd):
    """Responsibilities, expected sufficient statistics and the local ELBO."""
    dtype = p["m"].dtype
    xc = xc.astype(dtype)
    logits = _logits(p, xc, xd)
    logr = jax.nn.log_softmax(logits, axis=-1)
    r = jnp.exp(logr)
    M = p["disc"].shape[-1]
    stats = dict(
        n=r.sum(0),                                              # [K]
        sy=jnp.einsum("nk,nf->fk", r, xc, precision=HI),
        syy=jnp.einsum("nk,nf->fk", r, xc * xc, precision=HI),
        disc=(jnp.einsum("nk,ndm->dkm", r, jax.nn.one_hot(xd, M, dtype=dtype),
                         precision=HI) if xd.shape[1] else None))
    elbo = (r * logits).sum() - (r * logr).sum()
    return stats, elbo


def update(pr, s):
    """Conjugate update: the prior plus the statistics."""
    n = s["n"][None]
    kk = pr["kk"] + n
    km = pr["kk"] * pr["m"]
    m = (km + s["sy"]) / kk
    b = pr["b"] + 0.5 * (s["syy"] + pr["m"] * km - m * kk * m)
    out = dict(mix=pr["mix"] + s["n"], m=m, kk=kk, a=pr["a"] + 0.5 * n,
               b=jnp.maximum(b, 1e-10), disc=pr["disc"])
    if s["disc"] is not None:
        out["disc"] = pr["disc"] + s["disc"]
    return out


def _dir_kl(q, p):
    return (gammaln(q.sum(-1)) - gammaln(q).sum(-1) - gammaln(p.sum(-1))
            + gammaln(p).sum(-1) + ((q - p) * _elogdir(q)).sum(-1))


def kl(q, p, with_disc: bool):
    e_lam = q["a"] / q["b"]
    kl_w = 0.5 * (jnp.log(q["kk"]) - jnp.log(p["kk"]) + p["kk"] / q["kk"]
                  + e_lam * p["kk"] * (q["m"] - p["m"]) ** 2 - 1.0)
    kl_g = ((q["a"] - p["a"]) * digamma(q["a"]) - gammaln(q["a"])
            + gammaln(p["a"]) + p["a"] * (jnp.log(q["b"]) - jnp.log(p["b"]))
            + q["a"] * (p["b"] - q["b"]) / q["b"])
    out = _dir_kl(q["mix"], p["mix"]) + (kl_w + kl_g).sum()
    if with_disc:
        out = out + _dir_kl(q["disc"] + 1e-12, p["disc"] + 1e-12).sum()
    return out


@jax.jit
def sweep(pr, post, xc, xd):
    s, le = local(post, xc, xd)
    new = update(pr, s)
    return new, le - kl(new, pr, xd.shape[1] > 0)


def fit(pr, post, xc, xd, max_sweeps: int, tol: float):
    """Sweeps until the ELBO settles: one unconditional sweep, then while
    fewer than ``max_sweeps`` and ``|dELBO| > tol * (|ELBO| + 1)``.
    Returns ``(posterior, elbo, sweeps)``."""
    prev = np.float32(-np.inf)
    n = 0
    while True:
        post, e = sweep(pr, post, xc, xd)
        e = np.float32(e)
        delta = np.float32(abs(e - prev))
        n += 1
        prev = e
        if not (n < max_sweeps
                and delta > np.float32(tol) * (np.abs(e) + np.float32(1))):
            return post, float(e), n


# -- streaming Bayesian updating with the drift test -----------------------


def natural(p):
    """Natural coordinates, in which an update adds the statistics."""
    km = p["kk"] * p["m"]
    return dict(mix=p["mix"], kk=p["kk"], km=km, a=p["a"],
                bq=p["b"] + 0.5 * p["m"] * km, disc=p["disc"])


def from_natural(n):
    m = n["km"] / n["kk"]
    return dict(mix=n["mix"], m=m, kk=n["kk"], a=n["a"],
                b=jnp.maximum(n["bq"] - 0.5 * m * n["km"], 1e-10),
                disc=n["disc"])


def temper(p, base, rho: float):
    """Forgetting on drift: interpolate toward the base prior in natural
    coordinates."""
    a, b = natural(p), natural(base)
    return from_natural({k: rho * a[k] + (1.0 - rho) * b[k] for k in a})


def stream_call(base, chained, post, chunks, max_sweeps, tol, *,
                drift_threshold=5.0, forget=0.3, delta=0.05):
    """One call over several batches: per batch, score it under the
    current posterior, run the Page-Hinkley test (fresh for each call), on
    a firing temper the chained prior toward ``base``, then fit.  Returns
    ``(posterior, elbo of the last batch, sweeps per batch, firings)``."""
    f32 = np.float32
    mean = cum = cum_min = f32(0.0)
    t = 0
    sweeps, fired = [], []
    for xc, xd in chunks:
        _, le = local(post, xc, xd)
        score = f32(le) / f32(max(xc.shape[0], 1))
        t += 1
        mean = f32(mean + (score - mean) / f32(t))
        cum = f32(cum + (mean - score - f32(delta)))
        cum_min = min(cum_min, cum)
        drifted = bool(cum - cum_min > f32(drift_threshold))
        prior = temper(chained, base, forget) if drifted else chained
        if drifted:
            mean = cum = cum_min = f32(0.0)
            t = 0
        post, e, n = fit(prior, post, xc, xd, max_sweeps, tol)
        chained = post
        sweeps.append(n)
        fired.append(drifted)
    return post, e, sweeps, fired
