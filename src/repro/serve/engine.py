"""Batched request serving — the inference-side example driver.

Two serving surfaces:

* :class:`DecodeEngine` — LM continuous batching: a fixed batch of request
  slots decodes in lock-step (synchronized positions — the layout
  ``decode_32k``/``long_500k`` lower); finished requests free their slot for
  queued prompts.  Slot refill uses teacher-forced prefill via repeated
  decode steps (simple, cache-correct); a production system would run a
  separate prefill graph.

* :class:`PGMQueryEngine` — the probabilistic-query path.  Queries against a
  CLG ``BayesianNetwork`` queue up and, at ``flush()``, are grouped by
  evidence *schema* (the set of observed variable names).  Each group rides
  the leading batch axis of the junction-tree factor tables, so N exact
  queries sharing a schema cost ONE device call (``mode="exact"``, the
  infer_exact subsystem); ``mode="importance"`` serves the same API from
  the approximate sampler for throughput comparisons.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ModelConfig
from repro.data.stream import Batch
from repro.nn import transformer as T
from repro.serve.plan import PlanCache, PlanKey


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class DecodeEngine:
    def __init__(self, params, cfg: ModelConfig, batch: int, capacity: int,
                 sh: T.Shardings = T.NO_SHARD, eos: Optional[int] = None,
                 greedy: bool = True, seed: int = 0):
        self.params, self.cfg, self.sh = params, cfg, sh
        self.batch, self.capacity = batch, capacity
        self.eos = eos
        self.greedy = greedy
        self.key = jax.random.PRNGKey(seed)
        self.state = T.init_decode_state(params, cfg, batch, capacity, sh)
        self.queue: List[Request] = []
        self.active: List[Optional[Request]] = [None] * batch
        self._step = jax.jit(
            lambda st, tok: T.decode_step(params, st, tok, cfg, sh))
        self._pending_prefill: List[List[int]] = [[] for _ in range(batch)]
        self._tok = np.zeros((batch, 1), np.int32)

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def _fill_slots(self) -> None:
        for i in range(self.batch):
            if self.active[i] is None and self.queue:
                req = self.queue.pop(0)
                self.active[i] = req
                # prompt tokens are fed one per engine step (lock-step decode)
                self._pending_prefill[i] = list(req.prompt)
                self._tok[i, 0] = self._pending_prefill[i].pop(0) \
                    if self._pending_prefill[i] else 0

    def step(self) -> int:
        """One synchronized decode step for the whole batch.

        Returns the number of active requests."""
        self._fill_slots()
        if not any(self.active):
            return 0
        logits, self.state = self._step(self.state, jnp.asarray(self._tok))
        if self.greedy:
            nxt = np.asarray(logits[:, 0].argmax(-1), np.int32)
        else:
            self.key, sub = jax.random.split(self.key)
            nxt = np.asarray(
                jax.random.categorical(sub, logits[:, 0]), np.int32)
        for i, req in enumerate(self.active):
            if req is None:
                continue
            if self._pending_prefill[i]:
                # still teacher-forcing the prompt
                self._tok[i, 0] = self._pending_prefill[i].pop(0)
                continue
            tok = int(nxt[i])
            req.out.append(tok)
            self._tok[i, 0] = tok
            if (self.eos is not None and tok == self.eos) \
                    or len(req.out) >= req.max_new:
                req.done = True
                self.active[i] = None
        return sum(r is not None for r in self.active)

    def run(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue:
                break


# ---------------------------------------------------------------------------
# Exact-query serving path (infer_exact junction tree)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PGMQuery:
    qid: int
    target: str                       # variable whose posterior is requested
    evidence: Dict[str, float]
    payload: Optional[np.ndarray] = None      # temporal mode: [T, F] sequence
    result: Optional[np.ndarray] = None       # posterior table over target
    log_evidence: Optional[float] = None      # exact mode only
    done: bool = False


class PGMQueryEngine:
    """Schema-batched posterior queries over a CLG Bayesian network.

    ``mode="exact"`` routes through :class:`JunctionTreeEngine` — queries
    with the same evidence schema propagate together in one batched device
    call.  ``mode="importance"`` answers each query with likelihood
    weighting (one sampler run per query) behind the same API.
    ``mode="vmp"`` serves q(Z | x) from a fitted plate model
    (``repro.pgm_models``) via the jitted, chunk-bounded
    ``vmp.posterior_z`` — N fully-observed queries sharing a schema cost
    one compiled dispatch; evidence must cover every feature ``X{i}``.
    ``mode="temporal"`` serves filtered / h-step predictive hidden-state
    posteriors from a fitted HMM-family model (``pgm_models.dynamic``):
    queries carry a ``[T, F]`` sequence payload, bucket by (T, horizon),
    and ride one compiled factored-frontier program per bucket shape
    (``dynamic._temporal_serve``, posterior passed as an argument so model
    updates are never served from stale compiled constants).
    """

    def __init__(self, bn, *, mode: str = "exact", n_samples: int = 10_000,
                 use_pallas: Optional[bool] = None, seed: int = 0,
                 plan_cache: Optional[PlanCache] = None,
                 network_version: int = 0, pad_pow2: bool = False,
                 mesh=None, data_axes: Tuple[str, ...] = ("data",)) -> None:
        from repro.infer_exact import JunctionTreeEngine

        if mode not in ("exact", "importance", "vmp", "temporal"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode == "vmp":
            # ``bn`` is a plate Model with a discrete latent Z
            if not hasattr(bn, "cp") or bn.cp.layout.K <= 1:
                raise ValueError("mode='vmp' needs a plate Model with a "
                                 "discrete latent Z")
        if mode == "temporal" and not hasattr(bn, "filtered_posterior"):
            raise ValueError("mode='temporal' needs a fitted HMM-family "
                             "model (pgm_models.dynamic)")
        if mesh is not None and mode != "vmp":
            raise ValueError("mesh replica sharding is only wired for "
                             "mode='vmp' (the dvmp path)")
        self.bn = bn
        self.mode = mode
        self.n_samples = n_samples
        self.seed = seed
        self._use_pallas = use_pallas
        # pad exact-mode buckets to the next power of two (vmp/temporal
        # always do) so arbitrary batch sizes reuse a handful of compiled
        # plans.  Off by default: direct callers keep one-plan-per-size
        # compile accounting; the async serving tier turns it on.
        self.pad_pow2 = pad_pow2
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        # one PlanCache serves every mode; the serving tier passes a shared
        # instance so exact-JT / vmp / temporal plans share an LRU + counters
        self.plans = plan_cache if plan_cache is not None else PlanCache()
        self.network_version = network_version
        self._jt = (JunctionTreeEngine(bn, use_pallas=use_pallas,
                                       plan_cache=self.plans,
                                       network_version=network_version)
                    if mode == "exact" else None)
        self._queue: List[PGMQuery] = []
        self._next = 0

    # -- deprecated pre-plan-API cache views ---------------------------------

    @property
    def _vmp_caps(self) -> set:
        """Deprecated: compiled posterior_z batch capacities now live in
        ``self.plans`` as ``PlanKey(mode="vmp")`` entries."""
        warnings.warn("PGMQueryEngine._vmp_caps is deprecated; use "
                      "PGMQueryEngine.plans (repro.serve.plan.PlanCache)",
                      DeprecationWarning, stacklevel=2)
        return {k.batch_shape[0] for k in self.plans.keys()
                if k.mode == "vmp"
                and k.network_version == self.network_version}

    @property
    def _temporal_keys(self) -> set:
        """Deprecated: compiled (T, horizon, cap) buckets now live in
        ``self.plans`` as ``PlanKey(mode="temporal")`` entries."""
        warnings.warn("PGMQueryEngine._temporal_keys is deprecated; use "
                      "PGMQueryEngine.plans (repro.serve.plan.PlanCache)",
                      DeprecationWarning, stacklevel=2)
        return {(k.batch_shape[1], int(k.schema[1][1:]), k.batch_shape[0])
                for k in self.plans.keys() if k.mode == "temporal"
                and k.network_version == self.network_version}

    # -- model lifecycle -----------------------------------------------------

    def set_model(self, bn, *, network_version: Optional[int] = None) -> None:
        """Swap the served network/model in place (the hot-swap primitive).

        Bumps ``network_version`` (or sets it to the explicit one), so every
        plan compiled for the old model — whose CPDs are baked into the
        executable as compiled constants — stops hitting and ages out of
        the LRU.  Queued queries are answered by the NEW model on the next
        flush; the async tier drains old buckets first, then calls this.
        """
        self.bn = bn
        self.network_version = (self.network_version + 1
                                if network_version is None else network_version)
        if self._jt is not None:
            self._jt.set_model(bn, network_version=self.network_version)

    # -- query intake --------------------------------------------------------

    def _validate(self, target: str, evidence: Dict[str, float],
                  payload: Optional[np.ndarray] = None
                  ) -> Tuple[Dict[str, float], Optional[np.ndarray]]:
        """Reject malformed queries and normalize (evidence, payload).

        Raises at SUBMIT time: flush() empties the queue before dispatch,
        so a late error would drop queued work.  The async serving tier
        calls this from its own submit path for the same reason.
        """
        if self.mode == "vmp":
            if target != "Z":
                raise ValueError(f"mode='vmp' serves the latent Z, "
                                 f"got target {target!r}")
            names = {f"X{i}" for i in range(self.bn.spec.n_features)}
            missing = names - set(evidence)
            if missing:
                raise ValueError(f"mode='vmp' needs fully observed features; "
                                 f"missing {sorted(missing)}")
            return dict(evidence), None
        if self.mode == "temporal":
            if target not in ("filter", "predict"):
                raise ValueError(f"mode='temporal' serves 'filter' or "
                                 f"'predict', got target {target!r}")
            arr = np.asarray(payload, np.float32)
            if arr.ndim != 2:
                raise ValueError("mode='temporal' needs a [T, F] sequence "
                                 "payload")
            h = int(evidence.get("horizon", 1 if target == "predict" else 0))
            if target == "filter":
                h = 0
            # value-carrying schema: same-(T, horizon) queries batch together
            return {"T": float(arr.shape[0]), "h": float(h)}, arr
        return dict(evidence), None

    def bucket_key(self, evidence: Dict[str, float]) -> tuple:
        """The schema bucket for (normalized) evidence — queries sharing a
        key ride one device call.  Temporal buckets are value-carrying
        ((T, horizon), not just the evidence NAMES): sequence length
        selects the program."""
        return (tuple(f"{k}{int(v)}" for k, v in sorted(evidence.items()))
                if self.mode == "temporal" else tuple(sorted(evidence)))

    def submit(self, target: str, evidence: Dict[str, float],
               payload: Optional[np.ndarray] = None) -> PGMQuery:
        ev, arr = self._validate(target, evidence, payload)
        q = PGMQuery(self._next, target, ev, arr)
        self._next += 1
        self._queue.append(q)
        return q

    def flush(self) -> List[PGMQuery]:
        """Answer every queued query; one device call per evidence schema.

        When obs is enabled each schema bucket is measured — queue depth,
        batch size, compile-vs-execute split (from the junction tree's
        ``last_run``), cache hit/miss and wall latency — as a
        ``serve.bucket`` span plus a ``serve_bucket`` event, with a
        ``serve_flush`` summary at the end.  Disabled (the default), this
        method runs the pre-obs code path with one integer compare per
        bucket added; the spans are profiler annotations at every level.
        """
        import time as _time

        done, queue = [], self._queue
        self._queue = []
        groups: Dict[tuple, List[PGMQuery]] = {}
        for q in queue:
            groups.setdefault(self.bucket_key(q.evidence), []).append(q)
        queue_depth = len(queue)
        with obs.span("serve.flush", mode=self.mode, n_queries=queue_depth,
                      n_buckets=len(groups)):
            for schema, qs in groups.items():
                t0 = _time.perf_counter_ns()
                with obs.span("serve.bucket", mode=self.mode,
                              schema=",".join(schema), batch=len(qs)):
                    if self.mode == "exact":
                        binfo = self._flush_exact(schema, qs)
                    elif self.mode == "vmp":
                        binfo = self._flush_vmp(schema, qs)
                    elif self.mode == "temporal":
                        binfo = self._flush_temporal(schema, qs)
                    else:
                        binfo = self._flush_importance(qs)
                if obs.enabled():
                    obs.emit("serve_bucket", mode=self.mode,
                             schema=",".join(schema), batch=len(qs),
                             queue_depth=queue_depth,
                             latency_us=(_time.perf_counter_ns() - t0) / 1e3,
                             **binfo)
                done.extend(qs)
        if obs.enabled():
            obs.emit("serve_flush", mode=self.mode, n_queries=queue_depth,
                     n_buckets=len(groups))
        # SUBMISSION order, not bucket order: callers pair results with
        # requests positionally, and qid is the submission sequence number
        done.sort(key=lambda q: q.qid)
        return done

    def _flush_exact(self, schema: tuple, qs: List[PGMQuery]) -> dict:
        B = len(qs)
        cap = (1 << max(B - 1, 0).bit_length()) if self.pad_pow2 else B
        ev = {}
        for n in schema:
            col = jnp.asarray([q.evidence[n] for q in qs])
            if cap != B:
                # pad with copies of row 0: rows are independent through the
                # tree, so real rows stay bit-identical to the unpadded run
                col = jnp.concatenate(
                    [col, jnp.broadcast_to(col[:1], (cap - B,))])
            ev[n] = col
        self._jt.set_evidence(ev)
        self._jt.run_inference()
        logz = np.atleast_1d(np.asarray(self._jt.log_evidence()))
        for target in {q.target for q in qs}:
            var = self.bn.dag.variables.by_name(target)
            post = np.atleast_2d(
                np.asarray(self._jt.posterior_discrete(var)))
            for b, q in enumerate(qs):
                if q.target == target:
                    q.result = post[b if post.shape[0] > 1 else 0]
                    q.log_evidence = float(logz[b if logz.size > 1 else 0])
                    q.done = True
        lr = self._jt.last_run or {}
        return {"cache_hit": bool(lr.get("cache_hit", False)),
                "compile_us": lr.get("compile_us", 0.0),
                "execute_us": lr.get("execute_us", 0.0)}

    def _flush_vmp(self, schema: tuple, qs: List[PGMQuery]) -> dict:
        """q(Z | x) for a schema group in ONE jitted posterior_z dispatch.

        Queries were validated at submit time (full evidence, target Z).
        With a ``mesh``, the batch is data-sharded over the mesh replicas
        via the dvmp ``shard_map`` path — N independent queries split
        across devices, one collective-free program."""
        model = self.bn
        spec = model.spec
        dm = spec.discrete_map
        cont_ids = [i for i in range(spec.n_features) if i not in dm]
        B = len(qs)
        # pad to the next power of two so arbitrary group sizes reuse a
        # handful of compiled posterior_z programs instead of one per size
        cap = 1 << max(B - 1, 0).bit_length()
        if self.mesh is not None:
            # shard_map needs cap % n_devices == 0; pow2 caps divide any
            # pow2 device count once cap >= n_devices
            n_dev = 1
            for a in self.data_axes:
                n_dev *= self.mesh.shape[a]
            cap = max(cap, n_dev)
        xc = np.zeros((cap, len(cont_ids)), np.float32)
        xd = np.zeros((cap, len(dm)), np.int32)
        for b, q in enumerate(qs):
            xc[b] = [q.evidence[f"X{i}"] for i in cont_ids]
            xd[b] = [q.evidence[f"X{i}"] for i in sorted(dm)]
        key = PlanKey(self.network_version, "vmp", schema, (cap,))
        cache_hit = self.plans.peek(key) is not None

        def build():
            if self.mesh is None:
                # posterior read through self.bn at run time: model updates
                # between flushes are never served from a stale closure
                return lambda xc_, xd_: self.bn.posterior_z(
                    Batch(xc_, xd_, jnp.ones(xc_.shape[0], jnp.float32)))
            from repro.core import dvmp as _dvmp
            m, axes = self.mesh, self.data_axes
            return lambda xc_, xd_: _dvmp.dvmp_posterior_z(
                self.bn.cp, self.bn.posterior, xc_, xd_, m, axes,
                backend=self.bn.backend, chunk=self.bn.chunk)

        plan = self.plans.get(key, build)
        post = np.asarray(plan.run(jnp.asarray(xc), jnp.asarray(xd)))
        for b, q in enumerate(qs):
            q.result = post[b]
            q.done = True
        return {"cache_hit": cache_hit, "compile_us": 0.0, "execute_us": 0.0}

    def _flush_temporal(self, schema: tuple, qs: List[PGMQuery]) -> dict:
        """Filtered / predictive state posteriors for one (T, horizon) bucket.

        All sequences in the bucket share T, so they stack into a single
        ``[cap, T, F]`` batch (cap = next power of two, mirroring the vmp
        path) and run through ONE jitted factored-frontier program
        (``dynamic._temporal_serve``); padded rows carry a zero mask."""
        from repro.pgm_models import dynamic as _dyn

        model = self.bn
        h = int(qs[0].evidence.get("h", 0))
        B = len(qs)
        cap = 1 << max(B - 1, 0).bit_length()
        T = qs[0].payload.shape[0]
        F = qs[0].payload.shape[1]
        xs = np.zeros((cap, T, F), np.float32)
        mask = np.zeros((cap, T), np.float32)
        for b, q in enumerate(qs):
            xs[b] = q.payload
            mask[b] = 1.0
        key = PlanKey(self.network_version, "temporal", schema, (cap, T))
        cache_hit = self.plans.peek(key) is not None

        def build():
            # model state read through self.bn at run time (swap-safe)
            return lambda xc_, mask_: _dyn._temporal_serve(
                self.bn.posterior, self.bn._design(xc_),
                self.bn._emission_target(xc_), mask_, horizon=h)

        plan = self.plans.get(key, build)
        beliefs, last = plan.run(jnp.asarray(xs), jnp.asarray(mask))
        beliefs, last = np.asarray(beliefs), np.asarray(last)
        for b, q in enumerate(qs):
            q.result = beliefs[b] if q.target == "filter" else last[b]
            q.done = True
        if not cache_hit and obs.enabled():
            obs.emit("temporal_plan", pipeline="factored_frontier",
                     batch=cap, T=T, S=int(model.S), horizon=h)
        return {"cache_hit": cache_hit, "compile_us": 0.0, "execute_us": 0.0}

    def _flush_importance(self, qs: List[PGMQuery]) -> dict:
        from repro.core.importance_sampling import ImportanceSampling

        for q in qs:
            inf = ImportanceSampling(n_samples=self.n_samples,
                                     seed=self.seed + q.qid)
            inf.set_model(self.bn)
            inf.set_evidence(q.evidence)
            inf.run_inference()
            var = self.bn.dag.variables.by_name(q.target)
            q.result = np.asarray(inf.posterior_discrete(var))
            q.done = True
        return {"cache_hit": False, "compile_us": 0.0, "execute_us": 0.0}
