"""Batch-streaming Bayesian learning — paper §2.3.

Implements:

* **Bayesian updating** (Eq. 3): the posterior after batch t-1 becomes the
  prior for batch t.  In natural-parameter space this is just carrying the
  accumulated suff-stats forward — constant memory per batch, never revisits
  old data.
* **Streaming Variational Bayes** (Broderick et al., 2013): each arriving
  batch is fitted with VMP sweeps against the chained prior.
* **Concept-drift detection** (Borchani et al., 2015 — "a novel probabilistic
  approach"): monitor the per-instance expected log-likelihood of each new
  batch under the current posterior with an exponential moving average +
  Page-Hinkley-style cumulative deviation test; on drift, the prior is
  *tempered* (forgetting factor) so the model re-adapts.

All of this works identically on one device or on the d-VMP mesh (pass
``mesh=``) — the paper's headline "same code multi-core or distributed".

Three drivers share one step body (:func:`_stream_step`):

* :func:`stream_update` — one host call per arriving batch (the online API);
* :func:`stream_fit` — T stacked batches in ONE jitted ``lax.scan`` with the
  drift test and prior tempering inside the scan body and the
  ``StreamState`` buffers donated, so the whole stream replay is a single
  resident device program (no per-batch host round-trip or dispatch);
* :class:`StreamPipeline` — batches that arrive one at a time, one device
  program per batch, each launched as soon as its batch is on the device,
  with the state carried (donated) on the device from one to the next.

``Model.update_model`` on a multi-chunk stream drives a
:class:`StreamPipeline`: the copy of chunk t+1 overlaps the fit of chunk t.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import vmp as V
from repro.core import dvmp
from repro.core.vmp import CompiledPlate, PlateParams
from repro.obs import sink as obs
from repro.obs.metrics import StreamBatchMetrics


class DriftState(NamedTuple):
    """Page-Hinkley statistics on per-instance held-out log-likelihood."""

    mean: jnp.ndarray      # running mean of the score
    cum: jnp.ndarray       # cumulative deviation
    cum_min: jnp.ndarray   # running min of cum
    t: jnp.ndarray


def drift_init() -> DriftState:
    z = jnp.asarray(0.0)
    return DriftState(mean=z, cum=z, cum_min=z, t=jnp.asarray(0))


def drift_update(state: DriftState, score: jnp.ndarray, *,
                 delta: float = 0.05) -> Tuple[DriftState, jnp.ndarray]:
    """score = mean per-instance E_q[log p(x)] of the new batch BEFORE update.

    Returns (new_state, ph_statistic); caller compares against a threshold
    lambda (e.g. 5.0) to flag drift.
    """
    t = state.t + 1
    mean = state.mean + (score - state.mean) / t
    cum = state.cum + (mean - score - delta)  # drops in score push cum UP
    cum_min = jnp.minimum(state.cum_min, cum)
    ph = cum - cum_min
    return DriftState(mean=mean, cum=cum, cum_min=cum_min, t=t), ph


@jax.named_scope("streaming.drift")
def drift_gate(dstate: DriftState, score: jnp.ndarray, chained, tempered, *,
               drift_threshold: float):
    """Page-Hinkley test + prior selection, as pure traced ops.

    Runs :func:`drift_update` on ``score``, then where-selects between the
    ``chained`` prior (no drift) and the ``tempered`` prior (detector
    fired), resetting the PH statistics on a firing.  Generic over the
    prior pytree — shared by the static streaming path
    (:func:`_stream_step`, ``PlateParams``) and the temporal
    ``pgm_models.dynamic.seq_stream_fit`` scan (``HMMPosterior``).

    Returns ``(prior, new_dstate, ph, drifted)``.
    """
    dstate, ph = drift_update(dstate, score)
    drifted = ph > drift_threshold
    prior = jax.tree_util.tree_map(
        lambda a, b: jnp.where(drifted, a, b), tempered, chained
    )
    # reset PH statistics after a drift signal
    dstate = jax.tree_util.tree_map(
        lambda r, k: jnp.where(drifted, r, k), drift_init(), dstate
    )
    return prior, dstate, ph, drifted


class StreamState(NamedTuple):
    prior: PlateParams     # chained prior  (Eq. 3 accumulation)
    post: PlateParams      # current posterior
    drift: DriftState
    n_seen: jnp.ndarray
    n_drifts: jnp.ndarray
    n_quarantined: jnp.ndarray   # batches skipped by the non-finite gate


def stream_init(prior: PlateParams, init: PlateParams) -> StreamState:
    """Fresh stream state.  The global params are COPIED (they are tiny)
    so the state owns its buffers — :func:`stream_fit` donates them."""
    copy = lambda tree: jax.tree_util.tree_map(jnp.array, tree)
    return StreamState(prior=copy(prior), post=copy(init), drift=drift_init(),
                       n_seen=jnp.asarray(0.0), n_drifts=jnp.asarray(0),
                       n_quarantined=jnp.asarray(0))


def tree_finite(tree) -> jnp.ndarray:
    """Scalar bool: every inexact leaf of ``tree`` is fully finite.

    Pure traced ops (an ``all``-reduce per leaf), so the streaming scans
    run it in-body as the quarantine health flag at negligible cost next
    to the VMP sweeps.  Integer/bool leaves are finite by construction and
    skipped."""
    ok = jnp.asarray(True)
    for leaf in jax.tree_util.tree_leaves(tree):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(leaf)))
    return ok


def _temper(params: PlateParams, base: PlateParams, rho: float) -> PlateParams:
    """Forgetting: geometric interpolation toward the base prior in natural
    coordinates — the 'power prior' used on drift detection."""
    from repro.core import svi

    nat = svi.to_natural(params)
    nat0 = svi.to_natural(base)
    mixed = jax.tree_util.tree_map(
        lambda a, b: rho * a + (1.0 - rho) * b, nat, nat0
    )
    return svi.from_natural(mixed)


def _stream_step(
    cp: CompiledPlate,
    base_prior: PlateParams,
    state: StreamState,
    xc: jnp.ndarray,
    xd: jnp.ndarray,
    mask: jnp.ndarray,
    drift_threshold: float,
    forget: float,
    backend: str,
    chunk: Optional[int],
    fit_fn,
) -> Tuple[StreamState, Dict[str, jnp.ndarray]]:
    """score -> (maybe) drift -> Bayesian update, as pure traced ops.

    THE step body, shared by the per-batch :func:`stream_update` API, the
    :func:`stream_fit` scan and the :class:`StreamPipeline` programs — all
    drivers run exactly this math.
    ``fit_fn(prior, post) -> (post, elbo, sweeps)`` supplies the inner VMP
    fit (jitted ``vmp_fit``, traced ``fit_loop`` or d-VMP sweeps).

    The info output is a :class:`StreamBatchMetrics` pytree computed
    in-graph (ELBO, drift statistic + event mask, tempering rho, effective
    instance count, sweeps-to-convergence) — scan-safe telemetry at zero
    extra cost (every gauge is a byproduct of ops the step already runs).
    """
    n_eff = mask.sum()

    # --- score the incoming batch under the CURRENT posterior ---------------
    stats_pre, _ = V.local_step(cp, state.post, xc, xd, mask,
                                backend=backend, chunk=chunk)
    score = stats_pre.local_elbo / jnp.maximum(n_eff, 1.0)
    # on drift: temper the chained prior back toward the base prior
    prior, dstate, ph, drifted = drift_gate(
        state.drift, score, state.prior,
        _temper(state.prior, base_prior, forget),
        drift_threshold=drift_threshold,
    )

    # --- streaming VB: VMP sweeps against the chained prior ------------------
    post, e, fit_sweeps = fit_fn(prior, state.post)

    # --- non-finite quarantine ----------------------------------------------
    # A poisoned batch (NaN/Inf rows, or a fit that diverged) must not
    # corrupt every subsequent batch through the chained posterior.  Same
    # static-shape HOLD trick as the fused fits' convergence flag: the
    # update is computed unconditionally above, then the carried state is
    # where-selected wholesale — an unhealthy batch is SKIPPED (posterior,
    # chained prior and Page-Hinkley state all held bit-exactly) and only
    # counted.  The drift gate's score feeds the PH state, so it is held
    # too: one NaN score would otherwise poison the detector forever.
    healthy = jnp.logical_and(jnp.isfinite(score), jnp.isfinite(e))
    healthy = jnp.logical_and(healthy, tree_finite(post))
    drifted = jnp.logical_and(drifted, healthy)
    sel = lambda new, old: jax.tree_util.tree_map(
        lambda a, b: jnp.where(healthy, a, b), new, old)

    new_state = StreamState(
        prior=sel(post, state.prior),  # Eq. 3: posterior -> tomorrow's prior
        post=sel(post, state.post),
        drift=sel(dstate, state.drift),
        n_seen=state.n_seen + jnp.where(healthy, n_eff, 0.0),
        n_drifts=state.n_drifts + drifted.astype(jnp.int32),
        n_quarantined=state.n_quarantined
        + jnp.logical_not(healthy).astype(jnp.int32),
    )
    zero = jnp.asarray(0.0)
    metrics = StreamBatchMetrics(
        elbo=jnp.where(healthy, e, zero),
        score=jnp.where(healthy, score, zero),
        ph=jnp.where(healthy, ph, zero),
        drifted=drifted, n_eff=n_eff,
        rho=jnp.where(drifted, forget, 1.0), sweeps=fit_sweeps,
        quarantined=jnp.logical_not(healthy),
    )
    return new_state, metrics.as_info()


def stream_update(
    cp: CompiledPlate,
    base_prior: PlateParams,
    state: StreamState,
    xc: jnp.ndarray,
    xd: jnp.ndarray,
    *,
    sweeps: int = 20,
    tol: float = 1e-4,
    drift_threshold: float = 5.0,
    forget: float = 0.3,
    mesh=None,
    data_axes: Tuple[str, ...] = ("data",),
    backend: str = "einsum",
    chunk: Optional[int] = None,
    mask: Optional[jnp.ndarray] = None,
) -> Tuple[StreamState, dict]:
    """Process one arriving batch: score -> (maybe) drift -> Bayesian update.

    Eq. 3: p(theta | X_1..X_t) ∝ p(X_t | theta) p(theta | X_1..X_{t-1}):
    the fit below uses ``state.prior`` (yesterday's posterior) as the prior.

    One host call per batch with the drift logic dispatched eagerly — the
    online API.  For a resident replay of many batches use
    :func:`stream_fit` (same step body, one device program).
    """
    if mask is None:
        mask = jnp.ones(xc.shape[0])

    if mesh is None:
        def fit_fn(prior, post):
            fit = V.vmp_fit(cp, prior, post, xc, xd, sweeps, tol,
                            mask, backend, chunk)
            return fit.post, fit.elbo, fit.sweep
    else:
        def fit_fn(prior, post):
            e = jnp.asarray(-jnp.inf)
            for _ in range(sweeps):  # bounded sweeps; dvmp_fit also available
                post, e = dvmp.dvmp_one_sweep(
                    cp, prior, post, xc, xd, mask, mesh, data_axes,
                    backend, chunk
                )
            return post, e, jnp.asarray(sweeps)

    new_state, info = _stream_step(cp, base_prior, state, xc, xd, mask,
                                   drift_threshold, forget, backend, chunk,
                                   fit_fn)
    if obs.enabled():
        obs.emit_stream_events(info)
    return new_state, info


@partial(
    jax.jit,
    static_argnums=(0,),
    static_argnames=("sweeps", "tol", "drift_threshold", "forget",
                     "backend", "chunk"),
    donate_argnums=(2,),
)
def _stream_fit_scan(cp, base_prior, state, xcs, xds, masks, *, sweeps, tol,
                     drift_threshold, forget, backend, chunk):
    def step(carry: StreamState, inp):
        xc, xd, mask = inp

        def fit_fn(prior, post):
            fit = V.fit_loop(cp, prior, post, xc, xd, mask, sweeps, tol,
                             backend, chunk)
            return fit.post, fit.elbo, fit.sweep

        return _stream_step(cp, base_prior, carry, xc, xd, mask,
                            drift_threshold, forget, backend, chunk, fit_fn)

    return jax.lax.scan(step, state, (xcs, xds, masks))


_FIT_KW = ("sweeps", "tol", "drift_threshold", "forget", "backend", "chunk")


class _Run(NamedTuple):
    """What one :class:`StreamPipeline` program hands the next."""

    state: StreamState
    cols: Dict[str, jnp.ndarray]   # per-batch info columns, [capacity]
    t: jnp.ndarray                 # batches absorbed so far


def _pipeline_step(cp, base_prior, state, cols, t, xc, xd, capacity, kw):
    """One batch through the :func:`stream_fit` scan, as a scan of one;
    its info (and ``passes``, the local-step passes: its sweeps plus the
    scoring pass) written at index ``t`` of the columns, which are made
    here when ``cols`` is None."""
    state, info = _stream_fit_scan(cp, base_prior, state, xc[None],
                                   xd[None], jnp.ones((1, xc.shape[0])),
                                   **kw)
    info = {k: v[0] for k, v in info.items()}
    info["passes"] = info["sweeps"] + 1
    if cols is None:
        cols = {k: jnp.zeros((capacity,), v.dtype) for k, v in info.items()}
    cols = {k: cols[k].at[t].set(v) for k, v in info.items()}
    return _Run(state, cols, t + 1), info["elbo"]


@partial(jax.jit, static_argnums=(0,),
         static_argnames=_FIT_KW + ("capacity",))
def _pipeline_first(cp, base_prior, chained, post, xc, xd, *, capacity,
                    **kw):
    """The first batch's program: the state is born here, from
    ``chained`` and ``post`` with no drift history; nothing is donated."""
    return _pipeline_step(cp, base_prior, stream_init(chained, post), None,
                          jnp.asarray(0), xc, xd, capacity, kw)


@partial(jax.jit, static_argnums=(0,), static_argnames=_FIT_KW,
         donate_argnums=(2,))
def _pipeline_next(cp, base_prior, run, xc, xd, **kw):
    """Every later batch's program, on the previous one's run, donated."""
    return _pipeline_step(cp, base_prior, run.state, run.cols, run.t, xc,
                          xd, None, kw)


@partial(jax.jit, static_argnums=(1,))
def _resize(cols, n):
    """The columns cut, or zero-padded, to length ``n``."""
    return {k: jnp.zeros((n,), c.dtype).at[:min(n, len(c))].set(c[:n])
            for k, c in cols.items()}


class StreamPipeline:
    """One streaming update over batches that arrive one at a time: the
    :func:`stream_fit` replay without the stack.

    Each :meth:`step` launches one device program on a batch already on
    the device and returns without waiting for it, so the caller stages
    the next batch while the device fits this one.  The
    :class:`StreamState` stays on the device from program to program: the
    first builds it from ``chained`` and ``post`` (:func:`stream_init`, no
    drift history), each later one takes the last one's, donated.  Every
    value read afterwards comes out of the programs: the per-batch info
    columns, written at index t in-graph, the last batch's ELBO and the
    instances absorbed.

    ``capacity`` is the number of batches expected, the columns' length.
    A stream that outruns it doubles them, and :meth:`finish` cuts them to
    the batches seen; one small program each, launched only on a wrong
    guess.  ``launches`` counts the fit programs, one a batch.
    """

    def __init__(self, cp: CompiledPlate, base_prior: PlateParams,
                 chained: PlateParams, post: PlateParams, *, capacity: int,
                 sweeps: int = 20, tol: float = 1e-4,
                 drift_threshold: float = 5.0, forget: float = 0.3,
                 backend: str = "einsum", chunk: Optional[int] = None):
        self._fixed = (cp, base_prior)
        self._start = (chained, post)
        self._kw = dict(sweeps=sweeps, tol=tol,
                        drift_threshold=drift_threshold, forget=forget,
                        backend=backend, chunk=chunk)
        self._capacity = max(int(capacity), 1)
        self._run: Optional[_Run] = None
        self._elbo = None
        self.launches = 0

    def step(self, xc: jnp.ndarray, xd: jnp.ndarray) -> None:
        """Launch the fit of one batch (``xc`` [B, F], ``xd`` [B, Fd])."""
        if self._run is None:
            self._run, self._elbo = _pipeline_first(
                *self._fixed, *self._start, xc, xd,
                capacity=self._capacity, **self._kw)
        else:
            if self.launches == self._capacity:
                self._capacity *= 2
                self._run = self._run._replace(
                    cols=_resize(self._run.cols, self._capacity))
            self._run, self._elbo = _pipeline_next(
                *self._fixed, self._run, xc, xd, **self._kw)
        self.launches += 1

    def finish(self):
        """``(state, info, elbo)`` after the last batch: the final
        :class:`StreamState`, the per-batch columns of :func:`stream_fit`
        plus ``passes``, each of leading dim T, and the last batch's ELBO,
        all on the device."""
        cols = self._run.cols
        if self.launches != self._capacity:
            cols = _resize(cols, self.launches)
        return self._run.state, cols, self._elbo


def stream_fit(
    cp: CompiledPlate,
    base_prior: PlateParams,
    state: StreamState,
    xcs: jnp.ndarray,
    xds: jnp.ndarray,
    masks: Optional[jnp.ndarray] = None,
    *,
    sweeps: int = 20,
    tol: float = 1e-4,
    drift_threshold: float = 5.0,
    forget: float = 0.3,
    backend: str = "einsum",
    chunk: Optional[int] = None,
    window: Optional[int] = None,
) -> Tuple[StreamState, Dict[str, jnp.ndarray]]:
    """Replay T stacked batches in ONE jitted ``lax.scan``.

    xcs: [T, B, F]; xds: [T, B, Fd]; masks: [T, B] (None = all real).
    Equivalent to T calls of :func:`stream_update` (same step body), but the
    whole stream is a single resident device program: the drift test,
    tempering and the inner VMP sweep loop all live inside the scan body,
    and the ``StreamState`` buffers are donated so the posterior is updated
    in place batch-over-batch.

    ``window=w`` bounds DEVICE memory for long streams: the stacked batches
    stay on the host (pass numpy arrays) and the scan replays them one
    device-sliced window of w batches at a time — ceil(T/w) dispatches
    instead of T, with only O(w * B) of the stream resident on device.
    ``window=None`` keeps the whole stream in one scan (fastest, largest
    footprint).  The tail window may retrace once if ``T % w != 0``.
    Batches that arrive one at a time, as ``Model.update_model``'s stream
    chunks do, go through :class:`StreamPipeline` instead: the same step,
    one program a batch, launched as soon as that batch is on the device
    rather than once the whole stack is.

    Returns the final state and per-batch info arrays ``{"elbo", "score",
    "ph", "drifted", "n_eff", "rho", "sweeps", "quarantined"}`` each of
    leading dim T (the :class:`StreamBatchMetrics` columns; ``drifted`` is
    the per-batch drift-event mask, ``quarantined`` marks non-finite
    batches skipped with the carried posterior held).  When obs is enabled
    (``REPRO_OBS``) the same columns are emitted host-side as
    ``stream_batch``/``drift``/``quarantine`` JSONL events AFTER the scan
    returns — the fused device program is byte-identical at every obs
    level.
    """
    # state is donated, but its leaves routinely alias each other and the
    # other operands (stream_init reuses the prior's buffers for state.prior
    # and symmetry_broken shares all-but-m with it); XLA rejects donating an
    # aliased buffer, so copy exactly the aliased (small, global) leaves
    seen = {id(leaf) for tree in (base_prior, xcs, xds, masks)
            for leaf in jax.tree_util.tree_leaves(tree)}

    def unalias(leaf):
        if id(leaf) in seen:
            return jnp.array(leaf)
        seen.add(id(leaf))
        return leaf

    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    state = jax.tree_util.tree_map(unalias, state)
    T = xcs.shape[0]
    if window is None or window >= T:
        if masks is None:
            masks = jnp.ones(xcs.shape[:2])
        state, info = _stream_fit_scan(cp, base_prior, state, xcs, xds,
                                       masks, sweeps=sweeps, tol=tol,
                                       drift_threshold=drift_threshold,
                                       forget=forget, backend=backend,
                                       chunk=chunk)
        if obs.enabled():
            obs.emit_stream_events(info)
        return state, info
    infos = []
    for t0 in range(0, T, window):
        xc_w = jnp.asarray(xcs[t0:t0 + window])
        xd_w = jnp.asarray(xds[t0:t0 + window])
        m_w = (jnp.ones(xc_w.shape[:2]) if masks is None
               else jnp.asarray(masks[t0:t0 + window]))
        state, info = _stream_fit_scan(cp, base_prior, state, xc_w, xd_w,
                                       m_w, sweeps=sweeps, tol=tol,
                                       drift_threshold=drift_threshold,
                                       forget=forget, backend=backend,
                                       chunk=chunk)
        infos.append(info)
    info = {k: jnp.concatenate([i[k] for i in infos]) for k in infos[0]}
    if obs.enabled():
        obs.emit_stream_events(info)
    return state, info
