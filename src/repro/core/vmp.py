"""Variational Message Passing (Winn & Bishop 2005) — the learning engine.

The engine performs CAVI over the Fig.-3 plate family (``dag.PlateSpec``):

    theta  ~ conjugate priors                       (global, shared)
    Z_i    ~ Cat(pi)                                (per-instance discrete latent)
    H_i    ~ N(0, I_L)                              (per-instance cont. latent)
    X_if   ~ N( w_{f,Z_i}^T d_if , lam_{f,Z_i}^-1 ) (continuous leaves; CLG Eq. 2)
    X_id   ~ Cat( theta_{d,Z_i} )                   (discrete leaves)

where the design vector d_if = [1, observed parents of f, H_i (masked)].

One VMP *sweep* = local step (update q(Z), q(H), emit expected sufficient
statistics — the "messages to global parameter nodes") + global step
(conjugate natural-parameter update).  This file is single-device; dvmp.py
wraps the local step in shard_map and psums the messages, exactly the d-VMP
scheme [Masegosa et al., 2016].

All functions are jit-compatible; the sweep loop uses ``jax.lax.while_loop``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import expfam as ef
from repro.core.dag import PlateSpec
from repro.obs.metrics import LocalStepMetrics


# ---------------------------------------------------------------------------
# Parameter / statistics pytrees
# ---------------------------------------------------------------------------


class PlateParams(NamedTuple):
    """Global variational posterior (and prior) over theta."""

    mix: ef.Dirichlet          # [K]        mixture weights (K=1 when no latent)
    reg: ef.MVNormalGamma      # [F, K, D]  one CLG regression per leaf/component
    disc: ef.Dirichlet         # [Fd, K, C] multinomial leaves (C = max card)


class PlateStats(NamedTuple):
    """Expected sufficient statistics — the d-VMP message pytree."""

    counts: jnp.ndarray        # [K]
    reg: ef.RegSuffStats       # [F, K, ...]
    disc: jnp.ndarray          # [Fd, K, C]
    n: jnp.ndarray             # scalar — #instances contributing
    local_elbo: jnp.ndarray    # scalar — sum of local ELBO terms


class PlateLayout(NamedTuple):
    """Static integer geometry derived from a PlateSpec (hashable, jit-static)."""

    F: int           # continuous leaves
    Fd: int          # discrete leaves
    K: int           # mixture components
    L: int           # continuous latent dim
    P: int           # max #observed parents
    D: int           # design dim = 1 + P + L
    C: int           # max discrete-leaf cardinality


def layout_of(spec: PlateSpec) -> PlateLayout:
    dm = spec.discrete_map
    F = spec.n_features - len(dm)
    Fd = len(dm)
    K = max(spec.latent_card, 1)
    L = spec.latent_dim
    P = max((len(spec.parent_idx(i)) for i in range(spec.n_features)), default=0)
    C = max(dm.values(), default=2)
    return PlateLayout(F=F, Fd=Fd, K=K, L=L, P=P, D=1 + P + L, C=C)


@dataclasses.dataclass(frozen=True, eq=False)  # eq=False: identity hash, jit-static
class CompiledPlate:
    """Static arrays derived from the spec (closed over by jitted fns).

    Continuous leaves are re-indexed 0..F-1 and discrete leaves 0..Fd-1; the
    data pipeline provides ``xc: [N, F]`` and ``xd: [N, Fd]`` accordingly.
    """

    spec: PlateSpec
    layout: PlateLayout
    parent_idx: jnp.ndarray    # [F, P] int — indices into xc columns
    parent_mask: jnp.ndarray   # [F, P]
    latent_mask: jnp.ndarray   # [F, L]
    card_mask: jnp.ndarray     # [Fd, C] — valid categories per discrete leaf


def compile_plate(
    spec: PlateSpec, latent_mask: Optional[jnp.ndarray] = None
) -> CompiledPlate:
    lay = layout_of(spec)
    dm = spec.discrete_map
    cont_ids = [i for i in range(spec.n_features) if i not in dm]
    cont_pos = {orig: new for new, orig in enumerate(cont_ids)}
    pidx = jnp.zeros((max(lay.F, 1), max(lay.P, 1)), jnp.int32)
    pmask = jnp.zeros((max(lay.F, 1), max(lay.P, 1)), jnp.float32)
    for new_f, orig_f in enumerate(cont_ids):
        for j, p in enumerate(spec.parent_idx(orig_f)):
            if p in dm:
                raise ValueError("observed parents must be continuous features")
            pidx = pidx.at[new_f, j].set(cont_pos[p])
            pmask = pmask.at[new_f, j].set(1.0)
    if latent_mask is None:
        lmask = jnp.ones((max(lay.F, 1), max(lay.L, 1)), jnp.float32)
    else:
        lmask = jnp.asarray(latent_mask, jnp.float32)
        lmask = lmask.reshape(max(lay.F, 1), max(lay.L, 1))
    cmask = jnp.zeros((max(lay.Fd, 1), lay.C), jnp.float32)
    for new_d, (orig, card) in enumerate(sorted(dm.items())):
        cmask = cmask.at[new_d, :card].set(1.0)
    return CompiledPlate(
        spec=spec, layout=lay, parent_idx=pidx, parent_mask=pmask,
        latent_mask=lmask, card_mask=cmask,
    )


def design_mask(cp: CompiledPlate) -> jnp.ndarray:
    """[F, D] — which design columns are live for each continuous leaf."""
    lay = cp.layout
    ones = jnp.ones((max(lay.F, 1), 1), jnp.float32)
    parts = [ones]
    if lay.P > 0:
        parts.append(cp.parent_mask[:, : lay.P])
    if lay.L > 0:
        parts.append(cp.latent_mask[:, : lay.L])
    return jnp.concatenate(parts, axis=1)


# ---------------------------------------------------------------------------
# Prior construction
# ---------------------------------------------------------------------------


def default_prior(cp: CompiledPlate, *, alpha0: float = 1.0, reg_scale: float = 1.0,
                  a0: float = 1.0, b0: float = 1.0) -> PlateParams:
    lay = cp.layout
    F, K, D, Fd, C = max(lay.F, 1), lay.K, lay.D, max(lay.Fd, 1), lay.C
    mix = ef.Dirichlet(jnp.full((K,), alpha0))
    eye = jnp.broadcast_to(jnp.eye(D) / reg_scale, (F, K, D, D))
    reg = ef.MVNormalGamma(
        m=jnp.zeros((F, K, D)),
        K=eye,
        a=jnp.full((F, K), a0),
        b=jnp.full((F, K), b0),
    )
    disc = ef.Dirichlet(
        jnp.full((Fd, K, C), alpha0) * cp.card_mask[:, None, :] + 1e-12
    )
    return PlateParams(mix=mix, reg=reg, disc=disc)


def symmetry_broken(prior: PlateParams, key: jax.Array, scale: float = 0.5
                    ) -> PlateParams:
    """Initial posterior: prior with jittered regression means (breaks the
    label symmetry that makes CAVI stall at the uniform fixed point)."""
    k1, k2 = jax.random.split(key)
    m = prior.reg.m + scale * jax.random.normal(k1, prior.reg.m.shape)
    disc = ef.Dirichlet(
        prior.disc.alpha * jnp.exp(0.1 * jax.random.normal(k2, prior.disc.alpha.shape))
    )
    return PlateParams(mix=prior.mix, reg=prior.reg._replace(m=m), disc=disc)


# ---------------------------------------------------------------------------
# Local step — compute q(Z), q(H) and emit expected sufficient statistics
# ---------------------------------------------------------------------------
#
# Two suff-stats backends share one math path:
#   backend="einsum"  — XLA einsum reductions (the reference; always exact);
#                       the leaf-shared latent-latent block is stored lazily
#                       as [K, L, L] (RegSuffStats.sxx_hh) and expanded once
#                       at the conjugate update
#   backend="pallas"  — kernels.clg_stats tiled-accumulation kernels; L > 0
#                       plates run the fused component-major
#                       clg_suffstats_latent kernel (design [obs, E[h|z=k]])
#                       (compiled on TPU, interpret fallback on CPU; oracles:
#                       kernels.ref.clg_suffstats_ref /
#                       clg_suffstats_latent_ref / clg_disc_counts_ref)
# and an instance-chunked driver (``chunk=``) scans the body over fixed-size
# instance blocks so the [N, F, K] intermediates (quad_oo, the sxx
# reductions) never materialize at full N; nothing [N, K, L, L]-shaped is
# formed on either backend.


BACKENDS = ("einsum", "pallas")


def default_backend() -> str:
    """'pallas' where the kernels compile natively (TPU or forced via
    REPRO_PALLAS_COMPILE=1), else 'einsum' — interpret-mode Pallas is
    correctness-grade only."""
    from repro.kernels import clg_stats

    return "einsum" if clg_stats._resolve_interpret(None) else "pallas"


def _observed_design(cp: CompiledPlate, xc: jnp.ndarray) -> jnp.ndarray:
    """[N, F, 1+P] observed part of the design vectors."""
    lay = cp.layout
    N = xc.shape[0]
    ones = jnp.ones((N, max(lay.F, 1), 1), xc.dtype)
    if lay.P == 0:
        return ones
    gathered = xc[:, cp.parent_idx]            # [N, F, P]
    return jnp.concatenate([ones, gathered * cp.parent_mask], axis=-1)


def _split_moments(cp: CompiledPlate, mom: ef.RegMoments):
    """Split regression moments into observed / latent blocks, applying masks."""
    lay = cp.layout
    Do = 1 + lay.P
    dmask = design_mask(cp)                                    # [F, D]
    mm = dmask[:, None, :, None] * dmask[:, None, None, :]     # [F,1,D,D]
    e_lamww = mom.e_lamww * mm
    e_lamw = mom.e_lamw * dmask[:, None, :]
    oo = e_lamww[..., :Do, :Do]
    oh = e_lamww[..., :Do, Do:]
    hh = e_lamww[..., Do:, Do:]
    wo = e_lamw[..., :Do]
    wh = e_lamw[..., Do:]
    return wo, wh, oo, oh, hh


def _latent_hh_shared(cp: CompiledPlate) -> bool:
    """True when every leaf sees the same latent dims (uniform latent mask):
    the latent-latent suff-stat block is then leaf-independent and the
    einsum backend stores it ONCE as a lazy [K, L, L] (``RegSuffStats.
    sxx_hh``) instead of broadcast per leaf.  Static: ``cp`` is concrete."""
    import numpy as np

    lm = np.asarray(cp.latent_mask)[:, : max(cp.layout.L, 1)]
    return bool((lm == lm[:1]).all())


def _reduce_reg(cp: CompiledPlate, obs: jnp.ndarray, y: jnp.ndarray,
                h_mean: jnp.ndarray, s_hh: jnp.ndarray, r: jnp.ndarray,
                backend: str):
    """Regression suff-stats reduction over instances.

    Returns ``(sxx, sxx_hh, sxy, syy)``; ``sxx_hh`` is None when ``sxx`` is
    the dense [F, K, D, D] matrix, or the lazy leaf-shared [K, L, L]
    latent-latent block (then ``sxx`` carries only the top [F, K, Do, D]
    observed rows — see :func:`repro.core.expfam.reg_dense`).

    ``backend="pallas"``: L == 0 routes through the k-independent
    ``clg_suffstats`` kernel; L > 0 routes the WHOLE reduction — observed,
    cross and latent blocks — through the fused component-major
    ``clg_suffstats_latent`` kernel (design [obs, E[h|z=k]] with the
    E[hh^T|z=k] covariance correction folded in), one pass over instances.
    ``backend="einsum"`` is the XLA reference; its latent-latent block is
    reduced once as [K, L, L] and never broadcast per leaf.
    """
    lay = cp.layout
    L = lay.L
    if L == 0:
        if backend == "pallas":
            from repro.kernels import clg_stats

            sxx, sxy, syy = clg_stats.clg_suffstats(obs, y, r)
        else:
            sxx = jnp.einsum("nfa,nfb,nk->fkab", obs, obs, r)
            sxy = jnp.einsum("nfa,nf,nk->fka", obs, y, r)
            syy = jnp.einsum("nf,nf,nk->fk", y, y, r)
        return sxx, None, sxy, syy
    if backend == "pallas":
        from repro.kernels import clg_stats

        sxx, sxy, syy = clg_stats.clg_suffstats_latent(obs, h_mean, y, r,
                                                       s_hh)
        return sxx, None, sxy, syy
    sxx_oo = jnp.einsum("nfa,nfb,nk->fkab", obs, obs, r)
    sxy_o = jnp.einsum("nfa,nf,nk->fka", obs, y, r)
    syy = jnp.einsum("nf,nf,nk->fk", y, y, r)
    sxx_oh = jnp.einsum("nfa,nkl,nk->fkal", obs, h_mean, r)
    sxx_top = jnp.concatenate([sxx_oo, sxx_oh], axis=-1)     # [F,K,Do,D]
    sxx_hh = (jnp.einsum("nkl,nkm,nk->klm", h_mean, h_mean, r)
              + r.sum(0)[:, None, None] * s_hh)              # [K,L,L]
    sxy = jnp.concatenate(
        [sxy_o, jnp.einsum("nkl,nf,nk->fkl", h_mean, y, r)], axis=-1
    )
    if not _latent_hh_shared(cp):
        # per-leaf latent masks (CustomGlobalLocalModel): the hh block is
        # leaf-dependent after masking — fall back to the dense matrix
        hh = jnp.broadcast_to(sxx_hh[None],
                              (max(lay.F, 1),) + sxx_hh.shape)
        bot = jnp.concatenate([jnp.swapaxes(sxx_oh, -1, -2), hh], axis=-1)
        return jnp.concatenate([sxx_top, bot], axis=-2), None, sxy, syy
    return sxx_top, sxx_hh, sxy, syy


def _reduce_disc(cp: CompiledPlate, xd: jnp.ndarray, r: jnp.ndarray,
                 backend: str) -> jnp.ndarray:
    """Discrete-leaf one-hot count reduction -> [Fd, K, C]."""
    lay = cp.layout
    if backend == "pallas":
        from repro.kernels import clg_stats

        counts = clg_stats.clg_disc_counts(xd, r, lay.C)
    else:
        onehot = jax.nn.one_hot(xd.astype(jnp.int32), lay.C)  # [N, Fd, C]
        counts = jnp.einsum("nfc,nk->fkc", onehot, r)
    return counts * cp.card_mask[:, None, :]


@jax.named_scope("vmp.disc_lookup")
def _disc_loglik(e_logtheta: jnp.ndarray, xd: jnp.ndarray) -> jnp.ndarray:
    """ll[n, k] = sum_f e_logtheta[f, k, xd[n, f]] -> [N, K].

    A compare-select chain per leaf and category, not a gather: the chain
    fuses into the elementwise work that consumes it, where a gather of
    [N, Fd] indices into the tiny [Fd, K, C] table runs as its own slow
    program with layout copies on either side.  Leaves are unrolled (the
    trace is O(Fd * C)): selected over [N, Fd, K] at once, the compiler
    keeps per-category masks and table slices apart, with a layout copy for
    each slice.  The numbers are ``take_along_axis``'s bit for bit: each
    selected entry is copied unchanged, the leaves are summed in order, an
    index in [-C, 0) wraps once, and one outside [-C, C) reads NaN, which
    the streaming quarantine relies on.
    """
    Fd, K, C = e_logtheta.shape
    xi = xd.astype(jnp.int32)
    ll = None
    for f in range(Fd):
        x = xi[:, f, None]                                     # [N, 1]
        leaf = jnp.full((xi.shape[0], K), jnp.nan, e_logtheta.dtype)
        for c in range(C):
            leaf = jnp.where((x == c) | (x == c - C), e_logtheta[f, :, c],
                             leaf)
        ll = leaf if ll is None else ll + leaf
    return ll


def _local_step_body(cp: CompiledPlate, params: PlateParams, xc: jnp.ndarray,
                     xd: jnp.ndarray, mask: jnp.ndarray,
                     r_fixed: Optional[jnp.ndarray], backend: str,
                     ) -> Tuple[PlateStats, jnp.ndarray]:
    """Local step on one (chunk of a) batch — see :func:`local_step`."""
    lay = cp.layout
    N = xc.shape[0]
    K, L, Do = lay.K, lay.L, 1 + lay.P

    e_logpi = ef.dirichlet_expected_logprob(params.mix)        # [K]
    mom = ef.mvnormalgamma_moments(params.reg)                 # [F, K, ...]
    wo, wh, oo, oh, hh = _split_moments(cp, mom)
    if lay.F == 0:
        # pure-discrete model: keep regression block inert (stats = 0)
        xc = jnp.zeros((N, 1), xd.dtype if xd.size else jnp.float32)
    obs = _observed_design(cp, xc)                             # [N, F, Do]
    y = xc.astype(obs.dtype)                                   # [N, F]

    # --- quadratic pieces that do not involve H -----------------------------
    # quad_oo[n,f,k] = o^T E[lam w_o w_o^T] o
    quad_oo = jnp.einsum("nfa,fkab,nfb->nfk", obs, oo, obs)
    lin_o = jnp.einsum("nfa,fka->nfk", obs, wo)                # o^T E[lam w_o]

    if L > 0:
        # --- q(H_i | Z_i = k): Gaussian, shared across leaves ---------------
        A = jnp.eye(L) + hh.sum(0)                             # [K, L, L]
        S = jnp.linalg.inv(A)                                  # [K, L, L]
        # b[n,k,l] = sum_f ( y E[lam w_h] - E[lam w_h w_o^T] o )
        b = jnp.einsum("nf,fkl->nkl", y, wh) - jnp.einsum(
            "fkal,nfa->nkl", oh, obs
        )
        h_mean = jnp.einsum("klm,nkm->nkl", S, b)              # [N, K, L]
        # E[hh^T | z=k] = S_k + E[h]E[h]^T splits every quadratic into an
        # instance-independent [K] piece plus a mean-outer-product piece, so
        # nothing [N, K, L, L]-shaped is ever materialized.
        quad_h = (jnp.einsum("fklm,klm->fk", hh, S)[None]
                  + jnp.einsum("fklm,nkl,nkm->nfk", hh, h_mean, h_mean))
        cross = 2.0 * jnp.einsum("nfa,fkal,nkl->nfk", obs, oh, h_mean)
        lin_h = jnp.einsum("nf,fkl,nkl->nfk", y, wh, h_mean) * 2.0
        # KL(q(H|z=k) || N(0, I)): covariance terms depend only on k
        _, logdet_s = jnp.linalg.slogdet(S)                    # [K]
        tr_s = jnp.trace(S, axis1=-2, axis2=-1)                # [K]
        kl_h = 0.5 * ((h_mean * h_mean).sum(-1)
                      + (tr_s - L - logdet_s)[None])           # [N, K]
    else:
        quad_h = jnp.zeros((N, max(lay.F, 1), K))
        cross = jnp.zeros_like(quad_h)
        lin_h = jnp.zeros_like(quad_h)
        kl_h = jnp.zeros((N, K))
        h_mean = jnp.zeros((N, K, 1))
        S = jnp.zeros((K, 1, 1))

    # E_q[log N(y_f | w^T d, lam^-1)] per leaf/component
    ll = 0.5 * (
        mom.e_loglam[None]
        - ef.LOG2PI
        - mom.e_lam[None] * (y * y)[..., None]
        + 2.0 * lin_o * y[..., None]
        + lin_h
        - quad_oo
        - cross
        - quad_h
    )                                                          # [N, F, K]
    ll_cont = ll.sum(1) if lay.F > 0 else jnp.zeros((N, K))

    # discrete leaves
    if lay.Fd > 0:
        e_logtheta = ef.dirichlet_expected_logprob(params.disc)  # [Fd, K, C]
        ll_disc = _disc_loglik(e_logtheta, xd)                   # [N, K]
    else:
        ll_disc = jnp.zeros((N, K))

    logits = e_logpi[None] + ll_cont + ll_disc - kl_h            # [N, K]
    if r_fixed is None:
        logr = jax.nn.log_softmax(logits, axis=-1)
        r = jnp.exp(logr) * mask[:, None]
    else:
        logr = jnp.log(jnp.maximum(r_fixed, 1e-30))
        r = r_fixed * mask[:, None]

    # --- messages to global parameter nodes ---------------------------------
    counts = r.sum(0)                                            # [K]

    # expected design outer products per leaf (masked dims handled by moments;
    # stats are masked below so padded dims keep their prior)
    sxx, sxx_hh, sxy, syy = _reduce_reg(cp, obs, y, h_mean, S, r, backend)
    nw = jnp.broadcast_to(counts[None], syy.shape)

    dmask = design_mask(cp)
    live = 1.0 if lay.F > 0 else 0.0  # inert regression block for pure-discrete
    Do = sxx.shape[-2]                # = D dense, 1 + P lazy (static)
    sxx = (sxx * dmask[:, None, :Do, None] * dmask[:, None, None, :] * live)
    if sxx_hh is not None:
        # lazy leaf-shared latent block; mask row is uniform across leaves
        # (guaranteed by _latent_hh_shared in _reduce_reg)
        lmask = dmask[0, Do:]
        sxx_hh = sxx_hh * lmask[None, :, None] * lmask[None, None, :] * live
    sxy = sxy * dmask[:, None, :] * live
    reg_stats = ef.RegSuffStats(sxx=sxx, sxy=sxy, syy=syy * live, n=nw * live,
                                sxx_hh=sxx_hh)

    if lay.Fd > 0:
        disc_counts = _reduce_disc(cp, xd, r, backend)
    else:
        disc_counts = jnp.zeros((1, K, lay.C))

    # local ELBO: sum_n [ sum_k r (logits) + H(r) ] with masked instances 0
    ent = ef.categorical_entropy(logr) * mask
    local_elbo = (r * logits).sum() + ent.sum()

    stats = PlateStats(
        counts=counts, reg=reg_stats, disc=disc_counts,
        n=mask.sum(), local_elbo=local_elbo,
    )
    return stats, r


@jax.named_scope("vmp.local_step")
def local_step(cp: CompiledPlate, params: PlateParams, xc: jnp.ndarray,
               xd: jnp.ndarray, mask: jnp.ndarray,
               r_fixed: Optional[jnp.ndarray] = None, *,
               backend: str = "einsum", chunk: Optional[int] = None,
               with_metrics: bool = False,
               ) -> Tuple[PlateStats, jnp.ndarray]:
    """One local VMP step on a batch.

    xc: [N, F] continuous leaves; xd: [N, Fd] int discrete leaves;
    mask: [N] 1.0 for real instances (0.0 pads — streaming tail batches);
    r_fixed: [N, K] — clamp q(Z) (supervised models: observed class labels).

    backend: "einsum" (XLA reference) or "pallas" (tiled-accumulation
    kernels); chunk: when set, instances are processed in blocks of this
    size by a ``lax.scan`` whose carry is the suff-stat pytree, so no
    [N, F, K] / [N, K, L, L] intermediate ever materializes at full N.
    Both knobs only change the reduction schedule, not the math.

    Returns the suff-stat message pytree and the responsibilities r: [N, K];
    with ``with_metrics=True`` (a static flag — jitted callers key on it)
    additionally returns an :class:`LocalStepMetrics` pytree whose
    ``chunk_n_eff`` holds the per-chunk effective instance counts ([1] when
    unchunked) — in-graph observability of the reduction schedule.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected {BACKENDS}")
    N = xc.shape[0]
    if chunk is None or chunk >= N:
        stats, r = _local_step_body(cp, params, xc, xd, mask, r_fixed,
                                    backend)
        if with_metrics:
            return stats, r, LocalStepMetrics(chunk_n_eff=mask.sum()[None])
        return stats, r

    nchunks = -(-N // chunk)
    pad = nchunks * chunk - N
    if pad:
        xc = jnp.pad(xc, ((0, pad), (0, 0)))
        xd = jnp.pad(xd, ((0, pad), (0, 0)))
        mask = jnp.pad(mask, (0, pad))          # pads masked out -> stats 0
        if r_fixed is not None:
            r_fixed = jnp.pad(r_fixed, ((0, pad), (0, 0)))
    xcs = xc.reshape(nchunks, chunk, xc.shape[1])
    xds = xd.reshape(nchunks, chunk, xd.shape[1])
    ms = mask.reshape(nchunks, chunk)
    rfs = (None if r_fixed is None
           else r_fixed.reshape(nchunks, chunk, r_fixed.shape[1]))

    def body(acc, inp):
        if rfs is None:
            xc_c, xd_c, m_c = inp
            rf_c = None
        else:
            xc_c, xd_c, m_c, rf_c = inp
        stats_c, r_c = _local_step_body(cp, params, xc_c, xd_c, m_c, rf_c,
                                        backend)
        return jax.tree_util.tree_map(jnp.add, acc, stats_c), r_c

    # first chunk seeds the accumulator (no zero-pytree construction);
    # chunk < N here, so nchunks >= 2 and the scan always has work
    stats0, r0 = _local_step_body(cp, params, xcs[0], xds[0], ms[0],
                                  None if rfs is None else rfs[0], backend)
    xs = ((xcs[1:], xds[1:], ms[1:]) if rfs is None
          else (xcs[1:], xds[1:], ms[1:], rfs[1:]))
    stats, rs = jax.lax.scan(body, stats0, xs)
    r = jnp.concatenate([r0[None], rs], axis=0).reshape(nchunks * chunk, -1)
    if with_metrics:
        return stats, r[:N], LocalStepMetrics(chunk_n_eff=ms.sum(axis=1))
    return stats, r[:N]


# ---------------------------------------------------------------------------
# Global step — conjugate update, Bayesian updating Eq. (3)
# ---------------------------------------------------------------------------


@jax.named_scope("vmp.global_update")
def global_update(prior: PlateParams, stats: PlateStats) -> PlateParams:
    """posterior natural params = prior natural params + summed messages."""
    mix = ef.dirichlet_update(prior.mix, stats.counts)
    reg = ef.mvnormalgamma_update(prior.reg, stats.reg)
    disc = ef.Dirichlet(prior.disc.alpha + stats.disc)
    return PlateParams(mix=mix, reg=reg, disc=disc)


def global_kl(q: PlateParams, p: PlateParams, lay: PlateLayout) -> jnp.ndarray:
    kl = ef.dirichlet_kl(q.mix, p.mix)
    kl = kl + ef.mvnormalgamma_kl(q.reg, p.reg).sum()
    if lay.Fd > 0:
        # guard: padded categories have alpha ~ 0 in both q and p -> kl 0
        kl = kl + ef.dirichlet_kl(
            ef.Dirichlet(q.disc.alpha + 1e-12), ef.Dirichlet(p.disc.alpha + 1e-12)
        ).sum()
    return kl


def elbo(cp: CompiledPlate, prior: PlateParams, post: PlateParams,
         stats: PlateStats) -> jnp.ndarray:
    """ELBO of the current (q(theta), q(Z), q(H)) triple.

    Uses the standard CAVI identity: local terms were computed against the
    *current* q(theta); the global penalty is KL(q(theta) || p(theta)) minus
    the correction for re-scoring expected-suff-stat terms, which cancels at
    the CAVI fixed point; we report local_elbo - KL (a valid lower bound
    surrogate whose monotonicity we test).
    """
    return stats.local_elbo - global_kl(post, prior, cp.layout)


# ---------------------------------------------------------------------------
# Batch VMP fit — lax.while_loop sweeps to convergence
# ---------------------------------------------------------------------------


class VMPState(NamedTuple):
    post: PlateParams
    elbo: jnp.ndarray
    delta: jnp.ndarray
    sweep: jnp.ndarray


def fit_loop(cp: CompiledPlate, prior: PlateParams, init: PlateParams,
             xc: jnp.ndarray, xd: jnp.ndarray, mask: jnp.ndarray,
             max_sweeps: int, tol: float, backend: str = "einsum",
             chunk: Optional[int] = None) -> VMPState:
    """Trace-level VMP sweep loop (no jit) — embedded by :func:`vmp_fit`,
    ``dvmp`` shard bodies and the ``streaming.stream_fit`` scan."""

    def sweep(state: VMPState) -> VMPState:
        stats, _ = local_step(cp, state.post, xc, xd, mask,
                              backend=backend, chunk=chunk)
        post = global_update(prior, stats)
        e = elbo(cp, prior, post, stats)
        return VMPState(post=post, elbo=e,
                        delta=jnp.abs(e - state.elbo), sweep=state.sweep + 1)

    def cond(state: VMPState):
        return jnp.logical_and(
            state.sweep < max_sweeps,
            state.delta > tol * (jnp.abs(state.elbo) + 1.0),
        )

    state0 = VMPState(post=init, elbo=jnp.asarray(-jnp.inf),
                      delta=jnp.asarray(jnp.inf), sweep=jnp.asarray(0))
    # one unconditional sweep, then loop
    state1 = sweep(state0)
    return jax.lax.while_loop(cond, sweep, state1)


@partial(jax.jit, static_argnums=(0, 5, 6, 8, 9))
def vmp_fit(cp: CompiledPlate, prior: PlateParams, init: PlateParams,
            xc: jnp.ndarray, xd: jnp.ndarray,
            max_sweeps: int = 100, tol: float = 1e-4,
            mask: Optional[jnp.ndarray] = None, backend: str = "einsum",
            chunk: Optional[int] = None) -> VMPState:
    """Run VMP sweeps on one (device-local) data set until ELBO converges."""
    if mask is None:
        mask = jnp.ones(xc.shape[0])
    return fit_loop(cp, prior, init, xc, xd, mask, max_sweeps, tol,
                    backend, chunk)


# ---------------------------------------------------------------------------
# Posterior inference in the learnt model (paper §3.4, VMP as inference)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(0,), static_argnames=("backend", "chunk"))
def posterior_z(cp: CompiledPlate, params: PlateParams, xc: jnp.ndarray,
                xd: jnp.ndarray, *, backend: str = "einsum",
                chunk: Optional[int] = None) -> jnp.ndarray:
    """q(Z | x) for a batch — the paper's getPosterior(HiddenVar).

    Jitted (keyed on the plate + batch shape): repeated serve-path calls
    dispatch one compiled program instead of retracing ``local_step``.
    ``chunk`` bounds memory for very large query batches.
    """
    mask = jnp.ones(xc.shape[0])
    _, r = local_step(cp, params, xc, xd, mask, backend=backend, chunk=chunk)
    return r
