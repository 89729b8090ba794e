"""Metrics pytrees — jit/scan-safe counters and gauges.

These are plain NamedTuples of arrays, so they ride through ``lax.scan``
carries/outputs, ``shard_map`` and donation like any other pytree: the
fused hot paths (``streaming._stream_fit_scan``, ``vmp.local_step``'s
chunked scan, the ``dvmp`` mesh programs) compute them IN-GRAPH and the
host decides after the fact whether to ship them to the sink
(``sink.emit_stream_events``).  Nothing here imports jax — the fields
are whatever arrays the caller puts in, which keeps ``repro.obs``
importable before jax is configured (``launch/dryrun.py`` sets XLA
flags pre-import).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple


class StreamBatchMetrics(NamedTuple):
    """Per-batch gauges from one streaming-VMP step (scalars in
    ``stream_update``; ``[T]`` stacked columns out of ``stream_fit``)."""

    elbo: Any      # final ELBO of the batch fit
    score: Any     # per-instance ELBO (drift statistic input)
    ph: Any        # Page-Hinkley statistic after the batch
    drifted: Any   # bool: did the detector fire on this batch
    n_eff: Any     # effective instance count (mask sum)
    rho: Any       # prior tempering factor applied (1.0 = no temper)
    sweeps: Any    # VMP sweeps-to-convergence for the batch fit
    quarantined: Any  # bool: non-finite batch skipped, carried posterior held

    def as_info(self) -> Dict[str, Any]:
        """The dict view that ``stream_fit``/``stream_update`` return
        (the public info API predates this pytree and stays dict-shaped)."""
        return dict(self._asdict())


class TemporalFitMetrics(NamedTuple):
    """Per-sweep gauges carried through the fused temporal VB-EM scans
    (``pgm_models.dynamic``): each field is a ``[sweeps]`` column stacked
    out of the ``lax.scan`` over sweeps — the temporal analog of
    :class:`StreamBatchMetrics`."""

    elbo: Any      # ELBO (loglik lower bound) after each sweep
    delta: Any     # |ELBO - previous ELBO| per sweep (0 once converged)
    active: Any    # bool: was this sweep actually run (vs held post-tol)

    def as_info(self) -> Dict[str, Any]:
        return dict(self._asdict())


class LocalStepMetrics(NamedTuple):
    """Optional output of ``vmp.local_step(..., with_metrics=True)``."""

    chunk_n_eff: Any   # [n_chunks] effective instances reduced per chunk


class DvmpMetrics(NamedTuple):
    """Optional output of ``dvmp.dvmp_fit(..., with_metrics=True)``."""

    shard_n: Any   # [n_shards] per-device effective instance counts
    sweeps: Any    # scalar: sweeps-to-convergence of the distributed fit


class UpdateCounters(NamedTuple):
    """What one ``Model.update_model`` call did, as its fit counted it
    in-graph (``model.last_update``).  The array fields are the fit's
    own, left unread on the device: keeping them costs no sync.
    ``launches`` is counted on the host by the path the call took."""

    sweeps: Any     # VMP sweeps per batch ([T] on the stream path)
    passes: Any     # local_step passes per batch: the sweeps, plus one
                    # scoring pass per batch on the stream path
    drifted: Any    # bool per batch: drift test fired ([T]); None where
                    # the path runs no drift test
    instances: Any  # instances absorbed by the call
    launches: int   # jitted fit programs the call launched, a host int:
                    # 1 for one fit (fused stream call, vmp_fit, dvmp_fit),
                    # one per window of a windowed replay, one vmp_fit per
                    # ragged chunk (stream_update), 0 for the closed form
