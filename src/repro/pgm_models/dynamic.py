"""Dynamic latent-variable models — paper Table 2, right column.

All models operate on ``SequenceBatch`` data ([B, T, ...]) and are learnt by
variational Bayesian EM:

  * HMM family — E-step = masked forward-backward (``lax.scan``), vmapped
    over sequences; M-step = conjugate Dirichlet / Normal-Gamma /
    MVNormalGamma updates from expected counts.  AR-HMM and IO-HMM reuse the
    CLG emission (regression on the previous observation / exogenous input).
  * Kalman filter (LDS) — E-step = Kalman smoothing; M-step = Bayesian
    linear regression (MVNormalGamma) for transition and emission rows.
  * Switching LDS — structured mean field q(s)q(h): factored-frontier pass
    for the switch chain, Kalman smoothing under averaged dynamics, Bayesian
    regression M-step per switch state.

Streaming (Eq. 3) works exactly as in the static case: posteriors chain —
:func:`seq_stream_fit` replays stacked sequence batches in ONE jitted scan
with the Page-Hinkley drift gate (``core.streaming.drift_gate``) and prior
tempering in-body, mirroring ``streaming.stream_fit``.

**Fused sweep loops.**  Every ``update_model`` defaults to ``fused=True``:
the whole VB-EM sweep loop runs as one jitted donated-buffer ``lax.scan``
over sweeps, with the masked forward-backward / Kalman smoother vmapped
over the sequence batch INSIDE the scan body and a
:class:`~repro.obs.metrics.TemporalFitMetrics` pytree (per-sweep ELBO,
delta, active flag) carried out of the scan.  Convergence inside the scan
is a hold: once ``|e - last| < tol (|e| + 1)`` the posterior stops being
adopted, bit-matching the host loop that breaks.  ``fused=False`` keeps
the seed-style eager per-sweep loop (same step functions, one dispatch per
sweep) as the parity/benchmark reference.

**Program caching.**  The fused fits are MODULE-LEVEL jitted functions, so
jax's shape-keyed jit cache is the program cache: repeated ``update_model``
calls with the same ``(B, T, F, S, dtypes)`` reuse the compiled program
instead of retracing (the seed retraced per call via per-instance
closures).  :func:`trace_counts` exposes trace-time counters bumped inside
each fused body — a compile happens iff the counter moves, which is the
CI non-retrace assertion.

**Suff-stats backends.**  The HMM-family and fHMM M-steps accept
``backend="einsum" | "pallas"``; ``pallas`` routes the responsibility-
weighted regression stats through ``kernels.ops.clg_seq_suffstats`` (the
``clg_stats`` kernel with the ``[B, T]`` leading dims flattened), sharing
the static plate's kernel and its interpret/compile policy.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import expfam as ef
from repro.core.factored_frontier import (Factorial2TBN,
                                          factored_frontier_filter,
                                          predictive_posterior)
from repro.data.stream import Attribute, DynamicDataStream, SequenceBatch, REAL
from repro.obs import sink as obs_sink
from repro.obs.metrics import StreamBatchMetrics, TemporalFitMetrics


# ---------------------------------------------------------------------------
# trace-time compile counters (the non-retrace CI assertion)
# ---------------------------------------------------------------------------

_TRACE_COUNTS: Dict[str, int] = {}


def _bump_trace(name: str) -> None:
    """Called INSIDE the jitted fused-fit bodies: runs once per trace
    (compile), never per cached execution — ``trace_counts()[name]``
    moving between two same-shape calls means the program was rebuilt."""
    _TRACE_COUNTS[name] = _TRACE_COUNTS.get(name, 0) + 1


def trace_counts() -> Dict[str, int]:
    """Snapshot of the fused-fit trace counters (per fused program name)."""
    return dict(_TRACE_COUNTS)


def _strong(tree):
    """Copy a pytree with weak types stripped (explicit-dtype ``jnp.array``).

    Two jobs at once for every fused-fit operand: (1) a weak-typed leaf
    (python-scalar initialised, e.g. ``jnp.asarray(0.3)``) and its
    strong-typed successor after one fit would key DIFFERENT compiled
    programs — the first refit would retrace; (2) the copy unaliases
    donated buffers (the chained prior IS the posterior after a fit, and
    XLA rejects donating an aliased or doubly-referenced buffer)."""
    return jax.tree_util.tree_map(
        lambda a: jnp.array(a, jnp.asarray(a).dtype), tree)


# ---------------------------------------------------------------------------
# masked forward-backward (shared by the HMM family)
# ---------------------------------------------------------------------------


def forward_backward(log_init: jnp.ndarray, log_trans: jnp.ndarray,
                     loglik: jnp.ndarray, mask: jnp.ndarray):
    """Single sequence. log_init [S], log_trans [S,S], loglik [T,S], mask [T].

    Returns (gamma [T,S], xi_sum [S,S], loglik_scalar).

    Padding semantics: masked steps HOLD the forward/backward state, their
    loglik values are never read (``where``-gated, so NaN/garbage padding
    is safe), and no transition is counted into or out of a padded step
    (``xi`` is masked by ``mask[t] * mask[t+1]``).  A LEFT-padded sequence
    seeds the recursion from ``log_init`` alone at its first observed step
    — the ``started`` flag below — rather than applying a spurious
    transition out of the padding."""
    S = log_init.shape[0]
    ll = jnp.where(mask[:, None] > 0, loglik, 0.0)   # NaN-safe padding

    def fstep(carry, inp):
        loga_prev, started = carry
        ll_t, m_t = inp
        trans_in = jax.nn.logsumexp(loga_prev[:, None] + log_trans, axis=0)
        # first observed step seeds from the initial distribution alone
        loga = jnp.where(started, trans_in, log_init) + ll_t
        loga = jnp.where(m_t > 0, loga, loga_prev)  # hold state over padding
        started = jnp.logical_or(started, m_t > 0)
        return (loga, started), loga

    _, logas = jax.lax.scan(
        fstep, (log_init, jnp.asarray(False)), (ll, mask))  # [T, S]
    logZ = jnp.where(mask.max() > 0, jax.nn.logsumexp(logas[-1]), 0.0)

    def bstep(carry, inp):
        logb_next = carry
        ll_t1, m_t1 = inp
        logb = jax.nn.logsumexp(
            log_trans + (ll_t1 + logb_next)[None, :], axis=1)
        logb = jnp.where(m_t1 > 0, logb, logb_next)
        return logb, logb

    logbT = jnp.zeros(S)
    _, logbs = jax.lax.scan(bstep, logbT, (ll[1:][::-1], mask[1:][::-1]))
    logbs = jnp.concatenate([logbs[::-1], logbT[None]], 0)  # [T, S]

    gamma = jax.nn.softmax(logas + logbs, axis=-1) * mask[:, None]

    # xi_t(i,j) ∝ a_t(i) T(i,j) l_{t+1}(j) b_{t+1}(j)
    logxi = (logas[:-1, :, None] + log_trans[None]
             + (ll[1:] + logbs[1:])[:, None, :])
    logxi = logxi - jax.nn.logsumexp(logxi, axis=(1, 2), keepdims=True)
    xi = jnp.exp(logxi) * (mask[1:] * mask[:-1])[:, None, None]
    return gamma, xi.sum(0), logZ


# ---------------------------------------------------------------------------
# HMM with (optionally regression-) Gaussian emissions
# ---------------------------------------------------------------------------


class HMMPosterior(NamedTuple):
    init: ef.Dirichlet        # [S]
    trans: ef.Dirichlet       # [S, S] rows
    emis: ef.MVNormalGamma    # [F, S, D] regression emission per feature/state


# -- class-agnostic step functions: every _HMMBase subclass reduces to a
#    (design d [B,T,F,D], target y [B,T,F]) pair, so ONE fused program per
#    shape serves the whole family ------------------------------------------


def _hmm_loglik(post: HMMPosterior, d: jnp.ndarray, y: jnp.ndarray
                ) -> jnp.ndarray:
    """[B, T, S] expected emission log-lik summed over features."""
    mom = ef.mvnormalgamma_moments(post.emis)     # [F, S, ...]
    quad = jnp.einsum("btfa,fsac,btfc->btfs", d, mom.e_lamww, d)
    lin = jnp.einsum("btfa,fsa->btfs", d, mom.e_lamw)
    ll = 0.5 * (
        mom.e_loglam[None, None] - ef.LOG2PI
        - mom.e_lam[None, None] * (y * y)[..., None]
        + 2.0 * y[..., None] * lin - quad
    )
    return ll.sum(2)


def _hmm_estep(post: HMMPosterior, d, y, mask):
    """Returns (gamma [B,T,S], xi [B,S,S], logZ [B])."""
    log_init = ef.dirichlet_expected_logprob(post.init)
    log_trans = ef.dirichlet_expected_logprob(post.trans)
    ll = _hmm_loglik(post, d, y)                  # [B, T, S]
    fb = jax.vmap(partial(forward_backward, log_init, log_trans))
    return fb(ll, mask)


def _hmm_mstep(prior: HMMPosterior, gamma, xi, d, y, mask,
               backend: str = "einsum") -> HMMPosterior:
    init = ef.Dirichlet(prior.init.alpha + gamma[:, 0].sum(0))
    trans = ef.Dirichlet(prior.trans.alpha + xi.sum(0))
    w = gamma * mask[..., None]                   # [B, T, S]
    if backend == "pallas":
        from repro.kernels import ops as kops
        sxx, sxy, syy = kops.clg_seq_suffstats(d, y, w)
    else:
        sxx = jnp.einsum("btfa,btfc,bts->fsac", d, d, w)
        sxy = jnp.einsum("btfa,btf,bts->fsa", d, y, w)
        syy = jnp.einsum("btf,btf,bts->fs", y, y, w)
    n = jnp.broadcast_to(w.sum((0, 1))[None], syy.shape)
    emis = ef.mvnormalgamma_update(
        prior.emis, ef.RegSuffStats(sxx, sxy, syy, n))
    return HMMPosterior(init=init, trans=trans, emis=emis)


def _hmm_fit_core(prior, post, d, y, mask, sweeps, tol, backend):
    """The sweep loop as a ``lax.scan`` with a convergence HOLD.

    Replicates the host loop exactly: the E/M step of the converging sweep
    is still adopted (the host ``break`` fires after the M-step), then the
    carry is held for the remaining scan steps.  Returns
    (post, last_elbo, TemporalFitMetrics with [sweeps] columns)."""

    def sweep(carry, _):
        post, last, done = carry
        gamma, xi, logZ = _hmm_estep(post, d, y, mask)
        e = logZ.sum()
        new_post = _hmm_mstep(prior, gamma, xi, d, y, mask, backend)
        conv = jnp.abs(e - last) < tol * (jnp.abs(e) + 1.0)
        active = jnp.logical_not(done)
        sel = lambda a, b: jnp.where(active, a, b)
        post = jax.tree_util.tree_map(sel, new_post, post)
        metrics = TemporalFitMetrics(
            elbo=jnp.where(active, e, last),
            delta=jnp.where(active, jnp.abs(e - last), 0.0),
            active=active,
        )
        last = jnp.where(active, jnp.where(conv, last, e), last)
        done = jnp.logical_or(done, conv)
        return (post, last, done), metrics

    carry0 = (post, -jnp.inf, jnp.asarray(False))
    (post, last, _), metrics = jax.lax.scan(
        sweep, carry0, None, length=sweeps)
    return post, last, metrics


@partial(jax.jit, static_argnames=("sweeps", "tol", "backend"),
         donate_argnums=(1,))
def _hmm_fit(prior, post, d, y, mask, *, sweeps, tol, backend):
    """One fused VB-EM fit for the whole HMM family.

    Module-level jit => the jit cache IS the program cache, keyed on the
    shapes/dtypes of (prior, post, d, y, mask) — i.e. (B, T, F, S, D,
    dtypes) — plus the static (sweeps, tol, backend).  ``post`` is donated
    (callers pass an unaliased copy)."""
    _bump_trace("hmm_fit")
    return _hmm_fit_core(prior, post, d, y, mask, sweeps, tol, backend)


def _hmm_filter_predict(post: HMMPosterior, d, y, mask, horizon: int):
    """Filtered beliefs + h-step predictive for a sequence batch.

    Returns (beliefs [B,T,S], last [B,S]) where ``last`` is the filtered
    distribution at the final step rolled ``horizon`` steps forward with no
    evidence (paper Code Fragment 14).  Pure function of the posterior —
    the serving layer jits it with the posterior as an ARGUMENT so model
    updates never serve stale compiled constants."""
    ll = _hmm_loglik(post, d, y)
    init = jax.nn.softmax(ef.dirichlet_expected_logprob(post.init))
    trans = jax.nn.softmax(ef.dirichlet_expected_logprob(post.trans), -1)
    model = Factorial2TBN(init=init[None], trans=trans[None])

    def one(seq_ll, seq_mask):
        beliefs, _ = factored_frontier_filter(
            model, seq_ll[:, None, :], seq_mask)
        return beliefs[:, 0]

    beliefs = jax.vmap(one)(ll, mask)
    last = beliefs[:, -1]
    if horizon > 0:
        last = jax.vmap(
            lambda b: predictive_posterior(model, b[None], horizon)[0])(last)
    return beliefs, last


@partial(jax.jit, static_argnames=("horizon",))
def _temporal_serve(post, d, y, mask, *, horizon):
    """The compiled temporal query program (``PGMQueryEngine``
    ``mode="temporal"``): one program per (B, T, F, S, horizon) bucket,
    cached by the module-level jit like the fused fits."""
    _bump_trace("temporal_serve")
    return _hmm_filter_predict(post, d, y, mask, horizon)


def _emit_fit_event(name: str, elbo, metrics: TemporalFitMetrics) -> None:
    if not obs_sink.enabled():
        return
    act = np.asarray(metrics.active)
    dl = np.asarray(metrics.delta)
    k = int(act.sum())
    obs_sink.emit("temporal_fit", model=name, sweeps=k, elbo=float(elbo),
                  delta=float(dl[max(k - 1, 0)]) if dl.size else 0.0)


class _HMMBase:
    """Shared machinery; subclasses define the emission design vector."""

    design_dim = 1  # bias only (plain Gaussian emission)

    def __init__(self, attributes, n_states: int = 2, *, seed: int = 0,
                 alpha0: float = 1.0, a0: float = 1.0, b0: float = 1.0):
        self.attributes = list(attributes)
        self.F = len([a for a in attributes if a.kind == REAL])
        self.S = n_states
        D = self.design_dim
        self.prior = HMMPosterior(
            init=ef.Dirichlet(jnp.full((self.S,), alpha0)),
            trans=ef.Dirichlet(jnp.full((self.S, self.S), alpha0)),
            emis=ef.MVNormalGamma(
                m=jnp.zeros((self.F, self.S, D)),
                K=jnp.broadcast_to(jnp.eye(D), (self.F, self.S, D, D)),
                a=jnp.full((self.F, self.S), a0),
                b=jnp.full((self.F, self.S), b0),
            ),
        )
        key = jax.random.PRNGKey(seed)
        m0 = self.prior.emis.m + jax.random.normal(
            key, self.prior.emis.m.shape)
        self.posterior = self.prior._replace(emis=self.prior.emis._replace(m=m0))
        self._chained_prior = self.prior

    # -- emission design: [B, T, F, D] / target: [B, T, F] -------------------

    def _design(self, xc: jnp.ndarray) -> jnp.ndarray:
        B, T, F = xc.shape
        return jnp.ones((B, T, F, 1), xc.dtype)

    def _emission_target(self, xc: jnp.ndarray) -> jnp.ndarray:
        return xc

    def _emission_loglik(self, post: HMMPosterior, xc: jnp.ndarray
                         ) -> jnp.ndarray:
        return _hmm_loglik(post, self._design(xc), self._emission_target(xc))

    def _estep(self, post: HMMPosterior, xc, mask):
        return _hmm_estep(post, self._design(xc),
                          self._emission_target(xc), mask)

    def _warm_start(self, xc: jnp.ndarray) -> None:
        """Data-driven symmetry breaking: bias term <- random observed
        frames (first fit only)."""
        if getattr(self, "_warm", False):
            return
        self._warm = True
        rng = np.random.default_rng(13)
        frames_all = xc[..., : self.F]   # emission columns (IOHMM: drops input)
        B, T, F = frames_all.shape
        picks = rng.integers(0, B * T, self.S)
        frames = np.asarray(frames_all.reshape(B * T, F))[picks]    # [S, F]
        m0 = np.array(self.posterior.emis.m)  # writable copy
        m0[:, :, 0] = frames.T
        self.posterior = self.posterior._replace(
            emis=self.posterior.emis._replace(m=jnp.asarray(m0)))

    # -- public API -----------------------------------------------------------

    def update_model(self, data, *, sweeps: int = 30, tol: float = 1e-5,
                     fused: bool = True, backend: str = "einsum") -> float:
        batch = data.collect() if isinstance(data, DynamicDataStream) else data
        xc, mask = batch.xc, batch.mask
        self._warm_start(xc)
        prior = self._chained_prior
        post = self.posterior
        d = self._design(xc)
        y = self._emission_target(xc)
        if fused:
            post, last, metrics = _hmm_fit(_strong(prior), _strong(post),
                                           d, y, mask,
                                           sweeps=sweeps, tol=tol,
                                           backend=backend)
            last = float(last)
        else:
            last, elbos, deltas = -np.inf, [], []
            for _ in range(sweeps):
                gamma, xi, logZ = _hmm_estep(post, d, y, mask)
                e = float(logZ.sum())
                post = _hmm_mstep(prior, gamma, xi, d, y, mask, backend)
                elbos.append(e)
                deltas.append(abs(e - last))
                if abs(e - last) < tol * (abs(e) + 1.0):
                    break
                last = e
            metrics = TemporalFitMetrics(
                elbo=np.asarray(elbos), delta=np.asarray(deltas),
                active=np.ones(len(elbos), bool))
        self.posterior = post
        self._chained_prior = post     # Eq. 3
        self.fit_metrics = metrics
        _emit_fit_event(type(self).__name__, last, metrics)
        return last

    def filtered_posterior(self, xc: jnp.ndarray, mask=None) -> jnp.ndarray:
        """[B, T, S] filtering distributions (Code Fragment 14 analog)."""
        if mask is None:
            mask = jnp.ones(xc.shape[:2])
        beliefs, _ = _hmm_filter_predict(
            self.posterior, self._design(xc), self._emission_target(xc),
            mask, 0)
        return beliefs

    def predictive(self, xc: jnp.ndarray, horizon: int,
                   mask=None) -> jnp.ndarray:
        """[B, S] state distribution ``horizon`` steps past the end of each
        sequence (getPredictivePosterior)."""
        if mask is None:
            mask = jnp.ones(xc.shape[:2])
        _, last = _hmm_filter_predict(
            self.posterior, self._design(xc), self._emission_target(xc),
            mask, horizon)
        return last

    def viterbi_states(self, xc) -> jnp.ndarray:
        g, _, _ = self._estep(self.posterior, xc, jnp.ones(xc.shape[:2]))
        return g.argmax(-1)

    def state_means(self) -> np.ndarray:
        """[S, F] emission means (bias term of the regression)."""
        return np.asarray(self.posterior.emis.m[:, :, 0]).T


class HiddenMarkovModel(_HMMBase):
    """Plain Gaussian-emission HMM."""


class AutoRegressiveHMM(_HMMBase):
    """Emission mean = w_s^T [1, x_{t-1,f}] (per feature) — AR(1) per state."""

    design_dim = 2

    def _design(self, xc):
        B, T, F = xc.shape
        prev = jnp.concatenate([jnp.zeros((B, 1, F), xc.dtype), xc[:, :-1]], 1)
        return jnp.stack([jnp.ones_like(prev), prev], -1)   # [B,T,F,2]


class InputOutputHMM(_HMMBase):
    """Emission mean = w_s^T [1, u_t] with exogenous input u (last column)."""

    design_dim = 2

    def __init__(self, attributes, n_states: int = 2, **kw):
        super().__init__(attributes, n_states, **kw)
        self.F = self.F - 1  # last REAL column is the input, not an emission
        # rebuild priors with the reduced F
        D = self.design_dim
        self.prior = self.prior._replace(emis=ef.MVNormalGamma(
            m=jnp.zeros((self.F, self.S, D)),
            K=jnp.broadcast_to(jnp.eye(D), (self.F, self.S, D, D)),
            a=jnp.full((self.F, self.S), kw.get("a0", 1.0)),
            b=jnp.full((self.F, self.S), kw.get("b0", 1.0)),
        ))
        key = jax.random.PRNGKey(kw.get("seed", 0))
        m0 = self.prior.emis.m + jax.random.normal(key, self.prior.emis.m.shape)
        self.posterior = self.prior._replace(
            emis=self.prior.emis._replace(m=m0))
        self._chained_prior = self.prior

    def _split(self, xc):
        return xc[..., :-1], xc[..., -1]

    def _emission_target(self, xc):
        return self._split(xc)[0]

    def _design(self, xc):
        y, u = self._split(xc)
        B, T, F = y.shape
        ones = jnp.ones((B, T, F, 1), xc.dtype)
        uu = jnp.broadcast_to(u[..., None, None], (B, T, F, 1))
        return jnp.concatenate([ones, uu], -1)


class DynamicNaiveBayes(_HMMBase):
    """Dynamic NB = HMM whose hidden class smooths over time; emissions are
    NB-style independent Gaussians — structurally our plain HMM (the paper's
    dynamic NB is exactly this 2TBN)."""


# ---------------------------------------------------------------------------
# sequence-batch streaming (Eq. 3 over SequenceBatch streams)
# ---------------------------------------------------------------------------


def _temper_hmm(params: HMMPosterior, base: HMMPosterior,
                rho: float) -> HMMPosterior:
    """Forgetting for the HMM posterior: geometric interpolation toward the
    base prior in natural-ish coordinates — Dirichlet alphas and the
    MVNormalGamma (K, K m, a, b) blocks are lerped, then the mean is
    recovered from the mixed precision (the temporal analog of
    ``streaming._temper``)."""
    lerp = lambda a, b: rho * a + (1.0 - rho) * b
    K = lerp(params.emis.K, base.emis.K)
    Km = lerp(jnp.einsum("...ac,...c->...a", params.emis.K, params.emis.m),
              jnp.einsum("...ac,...c->...a", base.emis.K, base.emis.m))
    m = jnp.linalg.solve(K, Km[..., None])[..., 0]
    emis = ef.MVNormalGamma(m=m, K=K, a=lerp(params.emis.a, base.emis.a),
                            b=lerp(params.emis.b, base.emis.b))
    return HMMPosterior(
        init=ef.Dirichlet(lerp(params.init.alpha, base.init.alpha)),
        trans=ef.Dirichlet(lerp(params.trans.alpha, base.trans.alpha)),
        emis=emis)


@partial(jax.jit,
         static_argnames=("sweeps", "tol", "drift_threshold", "forget",
                          "backend"),
         donate_argnums=(0,))
def _seq_stream_scan(state, base_prior, ds, ys, masks, *, sweeps, tol,
                     drift_threshold, forget, backend):
    from repro.core.streaming import drift_gate, tree_finite

    _bump_trace("seq_stream_fit")

    def step(carry, inp):
        d, y, mask = inp
        prior0, post0, dstate0, n_drifts, n_quar = carry
        n_eff = mask.sum()
        # score the batch under the CURRENT posterior (per-frame loglik)
        _, _, logZ = _hmm_estep(post0, d, y, mask)
        score = logZ.sum() / jnp.maximum(n_eff, 1.0)
        prior, dstate, ph, drifted = drift_gate(
            dstate0, score, prior0, _temper_hmm(prior0, base_prior, forget),
            drift_threshold=drift_threshold)
        post, last, fmetrics = _hmm_fit_core(
            prior, post0, d, y, mask, sweeps, tol, backend)
        # non-finite quarantine: a poisoned batch holds the carried
        # posterior/prior AND the PH state (a NaN score would corrupt the
        # detector) — same static-shape HOLD trick as the sweep scans.
        healthy = jnp.logical_and(jnp.isfinite(score), jnp.isfinite(last))
        healthy = jnp.logical_and(healthy, tree_finite(post))
        drifted = jnp.logical_and(drifted, healthy)
        sel = lambda new, old: jax.tree_util.tree_map(
            lambda a, b: jnp.where(healthy, a, b), new, old)
        zero = jnp.asarray(0.0)
        metrics = StreamBatchMetrics(
            elbo=jnp.where(healthy, last, zero),
            score=jnp.where(healthy, score, zero),
            ph=jnp.where(healthy, ph, zero),
            drifted=drifted, n_eff=n_eff,
            rho=jnp.where(drifted, forget, 1.0),
            sweeps=fmetrics.active.sum(),
            quarantined=jnp.logical_not(healthy),
        )
        carry = (sel(post, prior0),     # Eq. 3: posterior becomes the prior
                 sel(post, post0), sel(dstate, dstate0),
                 n_drifts + drifted.astype(jnp.int32),
                 n_quar + jnp.logical_not(healthy).astype(jnp.int32))
        return carry, metrics.as_info()

    (prior, post, dstate, n_drifts, n_quar), info = jax.lax.scan(
        step, state + (jnp.asarray(0, jnp.int32),
                       jnp.asarray(0, jnp.int32)), (ds, ys, masks))
    return (prior, post, dstate, n_drifts, n_quar), info


def seq_stream_fit(model, batches, *, sweeps: int = 10, tol: float = 1e-5,
                   drift_threshold: float = 5.0, forget: float = 0.3,
                   backend: str = "einsum"):
    """Replay a stream of ``SequenceBatch``es in ONE jitted ``lax.scan``.

    The temporal ``stream_fit``: per batch the scan body scores the
    incoming sequences under the current posterior, runs the Page-Hinkley
    drift gate (tempering the chained prior on a firing), fits with the
    fused sweep scan, and chains the posterior (Eq. 3).  ``model`` is any
    ``_HMMBase`` subclass; it is updated in place and the per-batch
    :class:`StreamBatchMetrics` columns are returned as an info dict (and
    emitted as ``stream_batch``/``drift`` JSONL events when obs is on).

    ``batches``: iterable of equal-shape ``SequenceBatch``es (e.g.
    ``DynamicDataStream.batches(B)``, which pads the tail batch).
    """
    batches = list(batches)
    if not batches:
        raise ValueError("seq_stream_fit needs at least one batch")
    model._warm_start(batches[0].xc)
    ds = jnp.stack([model._design(b.xc) for b in batches])
    ys = jnp.stack([model._emission_target(b.xc) for b in batches])
    masks = jnp.stack([b.mask for b in batches])
    from repro.core.streaming import drift_init
    state = _strong((model._chained_prior, model.posterior, drift_init()))
    (prior, post, _, n_drifts, n_quar), info = _seq_stream_scan(
        state, _strong(model.prior), ds, ys, masks, sweeps=sweeps, tol=tol,
        drift_threshold=drift_threshold, forget=forget, backend=backend)
    model.posterior = post
    model._chained_prior = post
    model.n_drifts = int(n_drifts)
    model.n_quarantined = int(n_quar)
    if obs_sink.enabled():
        obs_sink.emit_stream_events(info)
    return info


# ---------------------------------------------------------------------------
# factorial HMM — chain-parallel structured VB
# ---------------------------------------------------------------------------


def _fhmm_sweep(means, log_trans, log_init, noise, gammas, xc, mask, backend):
    """One Jacobi sweep over ALL chains at once.

    Every chain's residual is computed from the PREVIOUS sweep's gammas and
    means (chain-batched einsum), the per-chain forward-backward runs as a
    nested vmap over (chains, sequences), and the M-step is one batched
    responsibility-weighted regression (einsum or the clg_stats kernel)."""
    B, T, F = xc.shape
    C, S = means.shape[0], means.shape[1]
    contrib = jnp.einsum("btcs,csf->btcf", gammas, means)
    resid = xc[:, :, None, :] - (contrib.sum(2, keepdims=True) - contrib)
    diff = resid[:, :, :, None, :] - means[None, None]       # [B,T,C,S,F]
    ll = (-(0.5 / noise) * (diff ** 2).sum(-1)
          - 0.5 * F * jnp.log(2 * jnp.pi * noise))           # [B,T,C,S]

    def fb_chain(li, lt, ll_c):
        return jax.vmap(partial(forward_backward, li, lt))(ll_c, mask)

    g, xi, logZ = jax.vmap(fb_chain, in_axes=(0, 0, 2))(
        log_init, log_trans, ll)          # [C,B,T,S], [C,B,S,S], [C,B]
    gammas_new = jnp.moveaxis(g, 0, 2)    # [B,T,C,S]
    w = gammas_new * mask[:, :, None, None]
    if backend == "pallas":
        from repro.kernels import ops as kops
        dsn = jnp.ones((B, T, F, 1), xc.dtype)
        _, sxy, _ = jax.vmap(kops.clg_seq_suffstats,
                             in_axes=(None, 2, 2))(dsn, resid, w)
        num = jnp.swapaxes(sxy[..., 0], 1, 2)                # [C,S,F]
    else:
        num = jnp.einsum("btcs,btcf->csf", w, resid)
    denom = jnp.maximum(w.sum((0, 1)), 1e-6)[..., None]      # [C,S,1]
    means_new = num / denom
    xs_sum = xi.sum(1)                                       # [C,S,S]
    log_trans_new = (
        jnp.log(jnp.maximum(xs_sum + 1.0, 1e-6))
        - jnp.log(jnp.maximum(xs_sum.sum(-1, keepdims=True) + S, 1e-6)))
    return means_new, log_trans_new, gammas_new, logZ.sum()


@partial(jax.jit, static_argnames=("sweeps", "tol", "backend"),
         donate_argnums=(0,))
def _fhmm_fit(params, log_init, noise, xc, mask, *, sweeps, tol, backend):
    _bump_trace("fhmm_fit")
    means, log_trans, gammas = params

    def sweep(carry, _):
        means, log_trans, gammas, last, done = carry
        m2, lt2, g2, e = _fhmm_sweep(means, log_trans, log_init, noise,
                                     gammas, xc, mask, backend)
        conv = jnp.abs(e - last) < tol * (jnp.abs(e) + 1.0)
        active = jnp.logical_not(done)
        sel = lambda a, b: jnp.where(active, a, b)
        means, log_trans, gammas = jax.tree_util.tree_map(
            sel, (m2, lt2, g2), (means, log_trans, gammas))
        metrics = TemporalFitMetrics(
            elbo=jnp.where(active, e, last),
            delta=jnp.where(active, jnp.abs(e - last), 0.0),
            active=active)
        last = jnp.where(active, jnp.where(conv, last, e), last)
        return (means, log_trans, gammas, last,
                jnp.logical_or(done, conv)), metrics

    carry0 = (means, log_trans, gammas, -jnp.inf, jnp.asarray(False))
    (means, log_trans, gammas, last, _), metrics = jax.lax.scan(
        sweep, carry0, None, length=sweeps)
    return means, log_trans, gammas, last, metrics


class FactorialHMMModel:
    """Factorial HMM: C independent chains, joint Gaussian emission.

    Learnt with the factored-frontier mean-field: each chain's E-step sees
    the residual of the other chains' expected contributions (standard VB
    for fHMM, Ghahramani & Jordan 1997).  Chain updates are JACOBI (all
    chains from the previous sweep's state), which is what lets the fused
    path batch every chain through one nested-vmap forward-backward."""

    def __init__(self, attributes, n_chains: int = 2, n_states: int = 2,
                 *, seed: int = 0):
        self.F = len([a for a in attributes if a.kind == REAL])
        self.C, self.S = n_chains, n_states
        key = jax.random.PRNGKey(seed)
        self.means = jax.random.normal(key, (self.C, self.S, self.F))
        self.log_trans = jnp.log(jnp.full((self.C, self.S, self.S), 1.0 / n_states))
        self.log_init = jnp.log(jnp.full((self.C, self.S), 1.0 / n_states))
        self.noise = jnp.asarray(1.0)

    def update_model(self, data, *, sweeps: int = 15, tol: float = 0.0,
                     fused: bool = True, backend: str = "einsum") -> float:
        batch = data.collect() if isinstance(data, DynamicDataStream) else data
        xc, mask = batch.xc, batch.mask            # [B,T,F], [B,T]
        B, T, F = xc.shape
        gammas = jnp.full((B, T, self.C, self.S), 1.0 / self.S)
        if fused:
            params = _strong((self.means, self.log_trans, gammas))
            means, log_trans, gammas, last, metrics = _fhmm_fit(
                params, _strong(self.log_init), _strong(self.noise),
                xc, mask, sweeps=sweeps, tol=tol, backend=backend)
            last = float(last)
        else:
            last, elbos, deltas = -np.inf, [], []
            means, log_trans = self.means, self.log_trans
            for _ in range(sweeps):
                means, log_trans, gammas, e = _fhmm_sweep(
                    means, log_trans, self.log_init, self.noise, gammas,
                    xc, mask, backend)
                e = float(e)
                elbos.append(e)
                deltas.append(abs(e - last))
                if abs(e - last) < tol * (abs(e) + 1.0):
                    break
                last = e
            metrics = TemporalFitMetrics(
                elbo=np.asarray(elbos), delta=np.asarray(deltas),
                active=np.ones(len(elbos), bool))
        self.means, self.log_trans, self.gammas = means, log_trans, gammas
        self.fit_metrics = metrics
        _emit_fit_event(type(self).__name__, last, metrics)
        return last


# ---------------------------------------------------------------------------
# Kalman filter (LDS) and switching LDS
# ---------------------------------------------------------------------------


def _kalman_smooth(A, C, q, r, xs, mask):
    """Masked Kalman smoother for one sequence.

    xs [T, F], mask [T] -> (means [T, L], covs [T, L, L], pair moments
    [T-1, L, L], loglik).  Masked steps run the time update only (predict,
    no correction, no loglik contribution); their observation values are
    never read."""
    L = A.shape[0]
    F = C.shape[0]
    Q = q * jnp.eye(L)
    R = r * jnp.eye(F)

    def fstep(carry, inp):
        x_t, m_t = inp
        m, P, ll = carry
        mp = A @ m
        Pp = A @ P @ A.T + Q
        S = C @ Pp @ C.T + R
        Sinv = jnp.linalg.inv(S)
        Kg = Pp @ C.T @ Sinv
        innov = jnp.where(m_t > 0, x_t, 0.0) - C @ mp
        m_new = jnp.where(m_t > 0, mp + Kg @ innov, mp)
        P_new = jnp.where(m_t > 0, (jnp.eye(L) - Kg @ C) @ Pp, Pp)
        _, logdet = jnp.linalg.slogdet(S)
        ll_new = ll - jnp.where(
            m_t > 0,
            0.5 * (logdet + innov @ Sinv @ innov + F * jnp.log(2 * jnp.pi)),
            0.0)
        return (m_new, P_new, ll_new), (m_new, P_new, mp, Pp)

    m0 = jnp.zeros(L)
    P0 = jnp.eye(L)
    (mT, PT, ll), (fm, fP, pm, pP) = jax.lax.scan(
        fstep, (m0, P0, 0.0), (xs, mask))

    def bstep(carry, inp):
        ms_next, Ps_next = carry
        fm_t, fP_t, pm_t1, pP_t1 = inp
        J = fP_t @ A.T @ jnp.linalg.inv(pP_t1)
        ms = fm_t + J @ (ms_next - pm_t1)
        Ps = fP_t + J @ (Ps_next - pP_t1) @ J.T
        pair = J @ Ps_next  # Cov(h_t, h_{t+1})
        return (ms, Ps), (ms, Ps, pair)

    (m1, P1), (sm, sP, pair) = jax.lax.scan(
        bstep, (fm[-1], fP[-1]),
        (fm[:-1], fP[:-1], pm[1:], pP[1:]), reverse=True)
    sm = jnp.concatenate([sm, fm[-1][None]], 0)
    sP = jnp.concatenate([sP, fP[-1][None]], 0)
    return sm, sP, pair, ll


def _kf_mstep(sm, sP, pair, xs, mask):
    """Masked LDS M-step (regressions + noise).  With an all-ones mask this
    is numerically identical to the seed's unweighted sums."""
    B, T, L = sm.shape
    F = xs.shape[-1]
    w = mask
    wl = mask[:, 1:] * mask[:, :-1]
    Ehh = sP + sm[..., :, None] * sm[..., None, :]            # [B,T,L,L]
    Ehh_lag = pair + sm[:, :-1, :, None] * sm[:, 1:, None, :]
    # transition regression: h_t on h_{t-1}
    Sxx = jnp.einsum("bt,btlm->lm", wl, Ehh[:, :-1]) + jnp.eye(L)
    Sxy = jnp.einsum("bt,btlm->lm", wl, Ehh_lag)              # [L, L] (t,t+1)
    A = jnp.linalg.solve(Sxx, Sxy).T
    # emission regression: x_t on h_t
    Hxx = jnp.einsum("bt,btlm->lm", w, Ehh) + jnp.eye(L)
    Hxy = jnp.einsum("bt,btl,btf->lf", w, sm, xs)
    C = jnp.linalg.solve(Hxx, Hxy).T
    # noise variances
    n = jnp.maximum(w.sum(), 1.0)
    nl = jnp.maximum(wl.sum(), 1.0)
    resid = xs - jnp.einsum("fl,btl->btf", C, sm)
    r = jnp.maximum(
        jnp.einsum("bt,btf->", w, resid ** 2) / (n * F)
        + jnp.einsum("fl,bt,btlm,fm->", C, w, sP, C) / (n * F), 1e-4)
    dyn = sm[:, 1:] - jnp.einsum("lm,btm->btl", A, sm[:, :-1])
    q = jnp.maximum(jnp.einsum("bt,btl->", wl, dyn ** 2) / (nl * L), 1e-4)
    return A, C, q, r


@partial(jax.jit, static_argnames=("sweeps", "tol"), donate_argnums=(0,))
def _kf_fit(params, xs, mask, *, sweeps, tol):
    _bump_trace("kf_fit")
    A, C, q, r = params
    B, T, F = xs.shape
    L = A.shape[0]

    def sweep(carry, _):
        A, C, q, r, sm_keep, last, done = carry
        sm, sP, pair, lls = jax.vmap(
            partial(_kalman_smooth, A, C, q, r))(xs, mask)
        e = lls.sum()
        A2, C2, q2, r2 = _kf_mstep(sm, sP, pair, xs, mask)
        conv = jnp.abs(e - last) < tol * (jnp.abs(e) + 1.0)
        active = jnp.logical_not(done)
        sel = lambda a, b: jnp.where(active, a, b)
        A, C, q, r, sm_keep = jax.tree_util.tree_map(
            sel, (A2, C2, q2, r2, sm), (A, C, q, r, sm_keep))
        metrics = TemporalFitMetrics(
            elbo=jnp.where(active, e, last),
            delta=jnp.where(active, jnp.abs(e - last), 0.0),
            active=active)
        last = jnp.where(active, jnp.where(conv, last, e), last)
        return (A, C, q, r, sm_keep, last,
                jnp.logical_or(done, conv)), metrics

    sm0 = jnp.zeros((B, T, L), xs.dtype)
    carry0 = (A, C, q, r, sm0, -jnp.inf, jnp.asarray(False))
    (A, C, q, r, sm, last, _), metrics = jax.lax.scan(
        sweep, carry0, None, length=sweeps)
    return A, C, q, r, sm, last, metrics


class KalmanFilter:
    """Linear dynamical system learnt by Bayesian EM (Code Fragment 10).

    h_t = A h_{t-1} + w,  x_t = C h_t + v; q(A_rows), q(C_rows) are
    MVNormalGamma; q(h_{1:T}) from Kalman smoothing at the posterior mean.
    """

    def __init__(self, attributes, n_hidden: int = 2, *, seed: int = 0):
        self.F = len([a for a in attributes if a.kind == REAL])
        self.L = n_hidden
        key1, key2 = jax.random.split(jax.random.PRNGKey(seed))
        L, F = self.L, self.F
        self.A = 0.5 * jnp.eye(L) + 0.01 * jax.random.normal(key1, (L, L))
        self.C = jax.random.normal(key2, (F, L))
        self.q = jnp.asarray(0.3)   # process noise var
        self.r = jnp.asarray(0.3)   # obs noise var
        # Bayesian accumulators (prior precision for A and C rows)
        self.KA = jnp.broadcast_to(jnp.eye(L), (L, L, L))
        self.KC = jnp.broadcast_to(jnp.eye(L), (F, L, L))

    def set_num_hidden(self, n: int) -> "KalmanFilter":
        self.__init__([Attribute(f"G{i}", REAL) for i in range(self.F)], n)
        return self

    def _smooth(self, xs: jnp.ndarray):
        """xs [T, F] -> means [T, L], covs [T, L, L], pair moments, loglik."""
        return _kalman_smooth(self.A, self.C, self.q, self.r, xs,
                              jnp.ones(xs.shape[0]))

    def update_model(self, data, *, sweeps: int = 25, tol: float = 0.0,
                     fused: bool = True) -> float:
        batch = data.collect() if isinstance(data, DynamicDataStream) else data
        xs, mask = batch.xc, batch.mask              # [B, T, F], [B, T]
        B, T, F = xs.shape
        L = self.L
        if not getattr(self, "_warm", False):
            # PCA warm start: C <- top-L principal axes, A <- lag-1 regression
            self._warm = True
            flat = np.asarray(xs.reshape(B * T, F))
            flat = flat - flat.mean(0)
            _, _, vt = np.linalg.svd(flat, full_matrices=False)
            C0 = vt[:L].T                            # [F, L]
            scores = flat @ C0                       # [B*T, L]
            sc = scores.reshape(B, T, L)
            xlag = sc[:, :-1].reshape(-1, L)
            xnext = sc[:, 1:].reshape(-1, L)
            A0 = np.linalg.lstsq(xlag, xnext, rcond=None)[0].T
            self.C = jnp.asarray(C0, jnp.float32)
            self.A = jnp.asarray(A0, jnp.float32)
        if fused:
            params = _strong((self.A, self.C, self.q, self.r))
            A, C, q, r, sm, last, metrics = _kf_fit(
                params, xs, mask, sweeps=sweeps, tol=tol)
            self.A, self.C, self.q, self.r = A, C, q, r
            last = float(last)
        else:
            last, elbos, deltas = -np.inf, [], []
            sm = None
            for _ in range(sweeps):
                sm, sP, pair, lls = jax.vmap(partial(
                    _kalman_smooth, self.A, self.C, self.q, self.r))(xs, mask)
                e = float(lls.sum())
                self.A, self.C, self.q, self.r = _kf_mstep(
                    sm, sP, pair, xs, mask)
                elbos.append(e)
                deltas.append(abs(e - last))
                if abs(e - last) < tol * (abs(e) + 1.0):
                    break
                last = e
            metrics = TemporalFitMetrics(
                elbo=np.asarray(elbos), delta=np.asarray(deltas),
                active=np.ones(len(elbos), bool))
        self.smoothed = sm
        self.fit_metrics = metrics
        _emit_fit_event(type(self).__name__, last, metrics)
        return last

    def get_model(self):
        return {"A": self.A, "C": self.C, "q": self.q, "r": self.r}

    def filtered_states(self, xs: jnp.ndarray) -> jnp.ndarray:
        masks = jnp.ones(xs.shape[:2])
        sm, _, _, _ = jax.vmap(partial(
            _kalman_smooth, self.A, self.C, self.q, self.r))(xs, masks)
        return sm


def _slds_sweep(A, C, q, r, log_trans, resp, xs, mask):
    """One structured-VB sweep: q(h) under switch-averaged dynamics, q(s)
    from innovation logliks via the masked factored-frontier filter, then
    a STATE-BATCHED M-step (one [S]-batched linear solve instead of the
    seed's per-state Python loop)."""
    B, T, F = xs.shape
    S, L = A.shape[0], A.shape[1]
    w_all = resp * mask[..., None]
    Abar = jnp.einsum("bts,slm->lm", w_all, A) / jnp.maximum(mask.sum(), 1.0)
    sm, sP, pair, lls = jax.vmap(
        partial(_kalman_smooth, Abar, C, q, r))(xs, mask)
    e = lls.sum()
    # q(s): innovation loglik per switch state
    pred = jnp.einsum("slm,btm->btsl", A, sm[:, :-1])
    innov = sm[:, 1:, None, :] - pred                 # [B,T-1,S,L]
    loglik = -0.5 * (innov ** 2).sum(-1) / q
    loglik = jnp.concatenate([jnp.zeros((B, 1, S), xs.dtype), loglik], axis=1)
    model = Factorial2TBN(init=jnp.full((1, S), 1.0 / S),
                          trans=jnp.exp(log_trans)[None])

    def one(seq_ll, seq_mask):
        beliefs, _ = factored_frontier_filter(
            model, seq_ll[:, None, :], seq_mask)
        return beliefs[:, 0]

    resp2 = jax.vmap(one)(loglik, mask)
    # M-step: per-switch-state transition regression, batched over S
    Ehh = sP + sm[..., :, None] * sm[..., None, :]
    Ehh_lag = pair + sm[:, :-1, :, None] * sm[:, 1:, None, :]
    wl = mask[:, 1:] * mask[:, :-1]
    ws = resp2[:, 1:] * wl[..., None]                 # [B,T-1,S]
    Sxx = jnp.einsum("bts,btlm->slm", ws, Ehh[:, :-1]) + jnp.eye(L)
    Sxy = jnp.einsum("bts,btlm->slm", ws, Ehh_lag)
    A2 = jnp.swapaxes(jnp.linalg.solve(Sxx, Sxy), -1, -2)
    # shared emission + noises (as in KalmanFilter)
    Hxx = jnp.einsum("bt,btlm->lm", mask, Ehh) + jnp.eye(L)
    Hxy = jnp.einsum("bt,btl,btf->lf", mask, sm, xs)
    C2 = jnp.linalg.solve(Hxx, Hxy).T
    n = jnp.maximum(mask.sum(), 1.0)
    nl = jnp.maximum(wl.sum(), 1.0)
    resid = xs - jnp.einsum("fl,btl->btf", C2, sm)
    r2 = jnp.maximum(jnp.einsum("bt,btf->", mask, resid ** 2) / (n * F), 1e-4)
    dyn = sm[:, 1:] - jnp.einsum(
        "bts,slm,btm->btl", resp2[:, 1:], A2, sm[:, :-1])
    q2 = jnp.maximum(jnp.einsum("bt,btl->", wl, dyn ** 2) / (nl * L), 1e-4)
    return A2, C2, q2, r2, resp2, sm, e


@partial(jax.jit, static_argnames=("sweeps", "tol"), donate_argnums=(0,))
def _slds_fit(params, log_trans, xs, mask, *, sweeps, tol):
    _bump_trace("slds_fit")
    A, C, q, r, resp = params
    B, T, _ = xs.shape
    L = A.shape[1]

    def sweep(carry, _):
        A, C, q, r, resp, sm_keep, last, done = carry
        A2, C2, q2, r2, resp2, sm, e = _slds_sweep(
            A, C, q, r, log_trans, resp, xs, mask)
        conv = jnp.abs(e - last) < tol * (jnp.abs(e) + 1.0)
        active = jnp.logical_not(done)
        sel = lambda a, b: jnp.where(active, a, b)
        A, C, q, r, resp, sm_keep = jax.tree_util.tree_map(
            sel, (A2, C2, q2, r2, resp2, sm), (A, C, q, r, resp, sm_keep))
        metrics = TemporalFitMetrics(
            elbo=jnp.where(active, e, last),
            delta=jnp.where(active, jnp.abs(e - last), 0.0),
            active=active)
        last = jnp.where(active, jnp.where(conv, last, e), last)
        return (A, C, q, r, resp, sm_keep, last,
                jnp.logical_or(done, conv)), metrics

    sm0 = jnp.zeros((B, T, L), xs.dtype)
    carry0 = (A, C, q, r, resp, sm0, -jnp.inf, jnp.asarray(False))
    (A, C, q, r, resp, sm, last, _), metrics = jax.lax.scan(
        sweep, carry0, None, length=sweeps)
    return A, C, q, r, resp, sm, last, metrics


class SwitchingLDS:
    """Switching LDS: discrete switch s_t selects the dynamics matrix A_s.

    Structured mean-field: q(s) (factored frontier over the switch chain,
    using expected innovation likelihoods) x q(h) (Kalman smoothing under
    switch-averaged dynamics); M-step = responsibility-weighted regressions.
    """

    def __init__(self, attributes, n_states: int = 2, n_hidden: int = 2,
                 *, seed: int = 0):
        self.F = len([a for a in attributes if a.kind == REAL])
        self.S, self.L = n_states, n_hidden
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        self.A = (0.5 * jnp.eye(self.L)[None]
                  + 0.3 * jax.random.normal(k1, (self.S, self.L, self.L)))
        self.C = jax.random.normal(k2, (self.F, self.L))
        self.q = jnp.asarray(0.3)
        self.r = jnp.asarray(0.3)
        self.log_trans = jnp.log(
            0.9 * jnp.eye(self.S) + 0.1 / self.S)

    def update_model(self, data, *, sweeps: int = 10, tol: float = 0.0,
                     fused: bool = True) -> float:
        batch = data.collect() if isinstance(data, DynamicDataStream) else data
        xs, mask = batch.xc, batch.mask
        B, T, F = xs.shape
        S = self.S
        resp = jnp.full((B, T, S), 1.0 / S)
        if fused:
            params = _strong((self.A, self.C, self.q, self.r, resp))
            A, C, q, r, resp, sm, last, metrics = _slds_fit(
                params, _strong(self.log_trans), xs, mask,
                sweeps=sweeps, tol=tol)
            self.A, self.C, self.q, self.r = A, C, q, r
            last = float(last)
        else:
            last, elbos, deltas = -np.inf, [], []
            for _ in range(sweeps):
                (self.A, self.C, self.q, self.r, resp, sm, e) = _slds_sweep(
                    self.A, self.C, self.q, self.r, self.log_trans, resp,
                    xs, mask)
                e = float(e)
                elbos.append(e)
                deltas.append(abs(e - last))
                if abs(e - last) < tol * (abs(e) + 1.0):
                    break
                last = e
            metrics = TemporalFitMetrics(
                elbo=np.asarray(elbos), delta=np.asarray(deltas),
                active=np.ones(len(elbos), bool))
        self.resp = resp
        self.smoothed = sm
        self.fit_metrics = metrics
        _emit_fit_event(type(self).__name__, last, metrics)
        return last
