"""Model — the paper's ``latentvariablemodels.staticmodels.Model`` analog.

Subclasses override :meth:`build_spec` (the paper's ``buildDAG()``) to return
a ``PlateSpec`` (+ optional latent mask).  ``update_model`` accepts a
``DataStream``, a ``Batch`` or raw arrays and performs either batch VMP,
distributed d-VMP (``mesh=``) or streaming Bayesian updating (repeated calls
— Eq. 3), mirroring Code Fragments 7/9/12.
"""

from __future__ import annotations

import itertools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro import obs
from repro.core import dvmp, expfam as ef, vmp
from repro.core.dag import (BayesianNetwork, CLGCPD, DAG, MultinomialCPD,
                            PlateSpec, Variables)
from repro.data.stream import Attribute, Batch, DataStream, FINITE, REAL
from repro.obs.metrics import UpdateCounters


def _to_device(chunk):
    """One stream chunk ``(xc, xd)`` copied to the device."""
    xc, xd = chunk
    return jnp.asarray(xc, jnp.float32), jnp.asarray(xd)


class Model:
    def __init__(self, attributes: Sequence[Attribute], *, seed: int = 0,
                 backend: Optional[str] = None, chunk: Optional[int] = None,
                 **prior_kwargs) -> None:
        self.attributes = list(attributes)
        spec, latent_mask = self.build_spec()
        self.spec = spec
        self.cp = vmp.compile_plate(spec, latent_mask)
        self.prior = vmp.default_prior(self.cp, **prior_kwargs)
        self.posterior = vmp.symmetry_broken(self.prior, jax.random.PRNGKey(seed))
        self._chained_prior = self.prior  # Eq. 3 accumulator
        self.n_seen = 0
        self.last_update: Optional[UpdateCounters] = None
        # suff-stats reduction schedule (vmp.local_step): backend None ->
        # pallas where the kernels compile natively, einsum elsewhere
        self.backend = backend if backend is not None else vmp.default_backend()
        self.chunk = chunk

    # -- to be overridden ------------------------------------------------------

    def build_spec(self) -> Tuple[PlateSpec, Optional[jnp.ndarray]]:
        raise NotImplementedError

    def supervised_r(self, batch: Batch) -> Optional[jnp.ndarray]:
        """Return fixed responsibilities [N, K] for supervised models."""
        return None

    # -- data plumbing ----------------------------------------------------------

    def _as_batch(self, data, sharding=None) -> Batch:
        """``data`` as one Batch.  With ``sharding`` (the d-VMP data
        sharding) host data goes straight to its shards, so no device holds
        the whole set on the way; a Batch already placed so stays put."""
        if sharding is None:
            if isinstance(data, Batch):
                return data
            if isinstance(data, DataStream):
                return data.collect()
            xc = jnp.asarray(data, jnp.float32)
            return Batch(xc, jnp.zeros((xc.shape[0], 0), jnp.int32),
                         jnp.ones(xc.shape[0], jnp.float32))
        if isinstance(data, DataStream):
            xcs, xds = zip(*data.chunks())
            data = Batch(np.concatenate(xcs), np.concatenate(xds),
                         np.ones(sum(len(x) for x in xcs), np.float32))
        elif not isinstance(data, Batch):
            xc = np.asarray(data, np.float32)
            data = Batch(xc, np.zeros((xc.shape[0], 0), np.int32),
                         np.ones(xc.shape[0], np.float32))
        return jax.device_put(data, sharding)

    # -- learning (paper Code Fragments 7, 9, 12) --------------------------------

    def update_model(self, data, *, sweeps: int = 100, tol: float = 1e-5,
                     mesh=None, data_axes: Tuple[str, ...] = ("data",),
                     stream_window: Optional[int] = None) -> float:
        """Fit/refine the posterior on ``data``.

        Repeated calls implement Bayesian updating (Eq. 3): the previous
        posterior becomes the prior for the new data.  Returns the ELBO.

        A multi-batch ``DataStream`` (a source yielding several chunks)
        routes through ``streaming``: each chunk is copied to the device
        and its fit launched at once (``streaming.StreamPipeline``: the
        ``stream_fit`` step with the drift test and tempering, the state
        carried on the device from chunk to chunk), so the copy of chunk
        t+1 overlaps the fit of chunk t; ragged chunk shapes take the same
        path.  Single-chunk streams, raw arrays and ``Batch``es keep the
        one-shot VMP fit below.  ``stream_window=w`` instead stacks
        equal-shape chunks on the host and replays device-sliced windows
        of w batches through ``stream_fit``.

        The call is an ``update_model`` span (``repro.obs``) whose children
        are ``update_model.ingest`` (host data to device arrays),
        ``update_model.dispatch`` (the call into the fit) and
        ``update_model.wait`` (reading the ELBO and the instance count,
        which waits for the device; one transfer on the stream path).  On
        the stream path each chunk has its own ``.ingest`` and
        ``.dispatch``, interleaved, and a last ``.ingest`` finds the end of
        the stream.  The fit's in-graph counters are kept in
        :attr:`last_update` (:class:`~repro.obs.metrics.UpdateCounters`) as
        device arrays that nothing in the call reads.
        """
        with obs.span("update_model"):
            return self._update_model(data, sweeps, tol, mesh,
                                      tuple(data_axes), stream_window)

    def _update_model(self, data, sweeps, tol, mesh, data_axes,
                      window) -> float:
        with obs.span("update_model.ingest"):
            batch, chunks = self._ingest(data, mesh, data_axes)
        if batch is None:
            return self._update_model_stream(chunks, sweeps=sweeps, tol=tol,
                                             window=window,
                                             expected=data.n_instances)
        prior = self._chained_prior
        with obs.span("update_model.dispatch"):
            r_fixed = self.supervised_r(batch)
            if r_fixed is not None:
                # conjugate closed form: one local step + global update
                stats, _ = vmp.local_step(
                    self.cp, self.posterior, batch.xc, batch.xd, batch.mask,
                    r_fixed, backend=self.backend, chunk=self.chunk
                )
                post = vmp.global_update(prior, stats)
                elbo = vmp.elbo(self.cp, prior, post, stats)
                done = np.int32(0)
            else:
                if mesh is None:
                    st = vmp.vmp_fit(self.cp, prior, self.posterior,
                                     batch.xc, batch.xd, sweeps, tol,
                                     batch.mask, self.backend, self.chunk)
                else:
                    st = dvmp.dvmp_fit(self.cp, prior, self.posterior,
                                       batch.xc, batch.xd, mesh, data_axes,
                                       sweeps, tol, mask=batch.mask,
                                       backend=self.backend, chunk=self.chunk)
                post, elbo, done = st.post, st.elbo, st.sweep
            n = batch.mask.sum()
        self.last_update = UpdateCounters(
            sweeps=done, passes=done if r_fixed is None else np.int32(1),
            drifted=None, instances=n, launches=int(r_fixed is None))
        with obs.span("update_model.wait"):
            e = float(elbo)
            self.n_seen += int(n)
        self.posterior = post
        self._chained_prior = post      # Eq. 3: posterior -> next prior
        return e

    def _ingest(self, data, mesh, data_axes):
        """Host data to device arrays: ``(batch, None)`` for the one-shot
        fit, or ``(None, chunks)`` for the streaming path.  A stream is
        read one chunk ahead, to tell a one-chunk stream: ``chunks``
        iterates over the stream's ``(xc, xd)``, the first already on the
        device, the second pulled from the source, the rest not yet."""
        if (mesh is None and isinstance(data, DataStream)
                and type(self).supervised_r is Model.supervised_r):
            source = data.chunks()
            first = next(source, None)
            if first is not None:
                first = _to_device(first)
                second = next(source, None)
                if second is not None:
                    return None, itertools.chain([first, second], source)
                # single chunk: reuse it instead of re-running the source
                # (sources need not be restartable)
                xc, xd = first
                data = Batch(xc, xd, jnp.ones(xc.shape[0], jnp.float32))
        return self._as_batch(
            data, None if mesh is None
            else NamedSharding(mesh, PartitionSpec(tuple(data_axes)))), None

    def _update_model_stream(self, chunks, *, sweeps: int, tol: float,
                             window: Optional[int] = None,
                             expected: Optional[int] = None) -> float:
        """Streaming Bayesian updating over a chunked stream (``stream_fit``
        underneath ``update_model``).  Each chunk is copied to the device
        and its step launched before the next chunk is pulled
        (``streaming.StreamPipeline``), one launch a chunk; ``expected``
        (instances the stream declares) sizes the per-batch columns.  With
        ``window`` equal-shape chunks are stacked on the host and replayed
        by ``stream_fit`` a window at a time."""
        from repro.core import streaming

        kw = dict(sweeps=sweeps, tol=tol, backend=self.backend,
                  chunk=self.chunk)
        if window is not None:
            with obs.span("update_model.ingest"):
                chunks = list(chunks)
                xcs = [np.asarray(xc, np.float32) for xc, _ in chunks]
                xds = [np.asarray(xd) for _, xd in chunks]
            if len({(xc.shape, xd.shape) for xc, xd in zip(xcs, xds)}) == 1:
                with obs.span("update_model.dispatch"):
                    state = streaming.stream_init(self._chained_prior,
                                                  self.posterior)
                    state, info = streaming.stream_fit(
                        self.cp, self.prior, state, np.stack(xcs),
                        np.stack(xds), window=window, **kw)
                    # one scoring pass per batch before its sweeps
                    info["passes"] = info["sweeps"] + 1
                    elbo = info["elbo"][-1]
                return self._stream_done(state, info, elbo,
                                         -(-len(chunks) // window))
            chunks = iter(chunks)
        first = next(chunks)
        rows = max(len(first[0]), 1)
        pipe = streaming.StreamPipeline(
            self.cp, self.prior, self._chained_prior, self.posterior,
            capacity=max(2, -(-expected // rows) if expected else 2), **kw)
        xc, xd = first
        while True:
            with obs.span("update_model.dispatch"):
                pipe.step(xc, xd)
            with obs.span("update_model.ingest"):
                nxt = next(chunks, None)
                if nxt is None:
                    break
                xc, xd = _to_device(nxt)
        state, info, elbo = pipe.finish()
        if obs.enabled():
            obs.emit_stream_events(info)
        return self._stream_done(state, info, elbo, pipe.launches)

    def _stream_done(self, state, info, elbo, launches) -> float:
        self.last_update = UpdateCounters(
            sweeps=info["sweeps"], passes=info["passes"],
            drifted=info["drifted"],
            instances=state.n_seen, launches=launches)
        with obs.span("update_model.wait"):
            e, n = jax.device_get((elbo, state.n_seen))
            self.n_seen += int(n)
        self.posterior = state.post
        self._chained_prior = state.post
        return float(e)

    # -- queries -----------------------------------------------------------------

    def posterior_z(self, data) -> jnp.ndarray:
        batch = self._as_batch(data)
        # vmp.posterior_z is jitted (keyed on the plate): per-query serve
        # calls dispatch one compiled program instead of retracing
        return vmp.posterior_z(self.cp, self.posterior, batch.xc, batch.xd,
                               backend=self.backend, chunk=self.chunk)

    def get_model(self) -> vmp.PlateParams:
        return self.posterior

    # -- exact inference (infer_exact junction tree — HUGIN-link replacement)

    def to_bayesian_network(self) -> BayesianNetwork:
        """Export the posterior-mean point estimate as a concrete CLG
        ``BayesianNetwork``.

        Node names: the latent is ``"Z"`` (present when ``latent_card > 1``);
        feature ``i`` of the spec is ``"X{i}"``.  Models with a continuous
        latent ``H`` (FA/PPCA family) are not expressible as a finite node
        set and raise ``NotImplementedError``.
        """
        lay = self.cp.layout
        if self.spec.latent_dim > 0:
            raise NotImplementedError(
                "continuous latent H has no finite-node BN export")
        spec, p = self.spec, self.posterior
        dm = spec.discrete_map
        vs = Variables()
        z = vs.new_multinomial("Z", lay.K) if lay.K > 1 else None
        feats = {}
        for i in range(spec.n_features):
            feats[i] = (vs.new_multinomial(f"X{i}", dm[i]) if i in dm
                        else vs.new_gaussian(f"X{i}"))
        dag = DAG(vs)
        cpds = {}
        if z is not None:
            cpds["Z"] = MultinomialCPD(ef.dirichlet_mean(p.mix))
        cont_ids = [i for i in range(spec.n_features) if i not in dm]
        sigma2 = p.reg.b / p.reg.a                       # [F, K] E-style var
        for f, orig in enumerate(cont_ids):
            v = feats[orig]
            if z is not None:
                dag.add_parent(v, z)
            pa = spec.parent_idx(orig)
            for pi in pa:
                dag.add_parent(v, feats[pi])
            m = p.reg.m[f]                               # [K, 1 + P]
            alpha, beta = m[:, 0], m[:, 1:1 + len(pa)]
            s2 = sigma2[f]
            if z is None:                                # no discrete parent
                alpha, beta, s2 = alpha[0], beta[0], s2[0]
            cpds[v.name] = CLGCPD(alpha=alpha, beta=beta, sigma2=s2)
        for new_d, (orig, card) in enumerate(sorted(dm.items())):
            v = feats[orig]
            if z is not None:
                dag.add_parent(v, z)
            alpha = p.disc.alpha[new_d, :, :card]        # [K, card]
            table = alpha / alpha.sum(-1, keepdims=True)
            cpds[v.name] = MultinomialCPD(table if z is not None
                                          else table[0])
        return BayesianNetwork(dag, cpds)

    def posterior_exact(self, data, *, use_pallas=None) -> jnp.ndarray:
        """Exact p(Z | x) via the native junction-tree engine.

        ``data`` is either an evidence dict (name -> scalar or [B] array,
        names as in :meth:`to_bayesian_network`) or anything
        :meth:`posterior_z` accepts — a Batch/DataStream/array whose rows
        become one batched propagation (a single device call).

        This is the correctness oracle for the approximate engines: for
        plate models with a single discrete latent it must agree with
        :meth:`posterior_z` up to VMP convergence.
        """
        from repro.infer_exact import JunctionTreeEngine

        if self.cp.layout.K <= 1:
            raise ValueError("model has no discrete latent to query")
        bn = self.to_bayesian_network()
        if isinstance(data, dict):
            evidence = data
        else:
            batch = self._as_batch(data)
            dm = self.spec.discrete_map
            cont_ids = [i for i in range(self.spec.n_features)
                        if i not in dm]
            evidence = {f"X{orig}": batch.xc[:, f]
                        for f, orig in enumerate(cont_ids)}
            for new_d, (orig, _) in enumerate(sorted(dm.items())):
                evidence[f"X{orig}"] = batch.xd[:, new_d]
        eng = JunctionTreeEngine(bn, use_pallas=use_pallas)
        eng.set_evidence(evidence)
        eng.run_inference()
        return eng.posterior_discrete(bn.dag.variables.by_name("Z"))

    # -- pretty print (paper Code Fragment 8) --------------------------------------

    def __str__(self) -> str:
        p = self.posterior
        lay = self.cp.layout
        lines = [f"{type(self).__name__} (Bayesian posterior):"]
        if lay.K > 1:
            w = np.asarray(p.mix.alpha / p.mix.alpha.sum())
            lines.append(f"P(Hidden) follows a Multinomial\n  {w}")
        for f in range(lay.F):
            mu = np.asarray(p.reg.m[f, :, 0])
            var = np.asarray(p.reg.b[f] / p.reg.a[f])
            lines.append(
                f"P(X{f} | ...) follows a Normal|Multinomial"
            )
            for k in range(lay.K):
                lines.append(f"  Normal [ mu = {mu[k]:.6f}, var = {var[k]:.6f} ]"
                             f" | {{Hidden = {k}}}")
        return "\n".join(lines)
