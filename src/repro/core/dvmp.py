"""d-VMP — distributed Variational Message Passing [Masegosa et al., 2016].

The paper's distributed scheme (Flink/Spark in the original) has one key
structural property: in the Fig.-3 plate family every *global* parameter node
receives, per VMP sweep, a message that is the SUM over data instances of
per-instance expected sufficient statistics, while *local* latent posteriors
(q(Z_i), q(H_i)) depend only on the instance's own data and the current
global posterior.  Hence:

    worker w:  stats_w = local_step(theta, data shard w)        (embarrassing)
    runtime :  stats   = all_reduce_sum(stats_w)                (one collective)
    driver  :  theta'  = conjugate_update(prior, stats)         (replicated)

On a TPU pod this is a `shard_map` over the data mesh axes with a single
`jax.lax.psum` of the suff-stat pytree per sweep — the Flink reduce becomes
an ICI all-reduce.  Local latents never leave their shard, which is what let
the paper scale to models with >1e9 (local-latent) nodes.

The sweep loop itself lives *inside* the shard_map body (a
``lax.while_loop``), so a full fit is ONE XLA program: sweeps are separated
by psums, not by host round-trips — strictly better than the paper's
per-iteration Flink superstep barrier.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core import vmp as V
from repro.core.vmp import CompiledPlate, PlateParams, PlateStats, VMPState
from repro.obs.metrics import DvmpMetrics


def _psum_stats(stats: PlateStats, axes) -> PlateStats:
    return jax.tree_util.tree_map(lambda s: jax.lax.psum(s, axes), stats)


# ---------------------------------------------------------------------------
# Program caches.  Building a fresh ``shard_map`` + ``jax.jit`` wrapper per
# call forced a retrace (and on the streaming path, one retrace PER ARRIVING
# BATCH).  The wrappers are pure functions of (cp, mesh, data_axes) plus the
# python scalars closed over by the body, so we build each program once per
# key — ``CompiledPlate`` hashes by identity and ``Mesh`` is hashable; jax's
# own jit cache then handles shape/dtype variation.  ``lru_cache`` bounds
# retention for long-lived processes that build plates/meshes dynamically.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _fit_program(cp: CompiledPlate, mesh: Mesh, data_axes: Tuple[str, ...],
                 max_sweeps: int, tol: float, backend: str,
                 chunk: Optional[int], with_metrics: bool = False):
    dspec = P(data_axes)
    rep = P()

    @partial(
        shard_map, mesh=mesh,
        in_specs=(rep, rep, dspec, dspec, dspec),
        out_specs=(rep, rep) if with_metrics else rep,
        check_vma=False,
    )
    def fit_shard(prior_, init_, xc_, xd_, mask_):
        def sweep(state: VMPState) -> VMPState:
            stats, _ = V.local_step(cp, state.post, xc_, xd_, mask_,
                                    backend=backend, chunk=chunk)
            stats = _psum_stats(stats, data_axes)      # the d-VMP collective
            post = V.global_update(prior_, stats)
            e = V.elbo(cp, prior_, post, stats)
            return VMPState(post=post, elbo=e,
                            delta=jnp.abs(e - state.elbo), sweep=state.sweep + 1)

        def cond(state: VMPState):
            return jnp.logical_and(
                state.sweep < max_sweeps,
                state.delta > tol * (jnp.abs(state.elbo) + 1.0),
            )

        s0 = VMPState(post=init_, elbo=jnp.asarray(-jnp.inf),
                      delta=jnp.asarray(jnp.inf), sweep=jnp.asarray(0))
        st = jax.lax.while_loop(cond, sweep, sweep(s0))
        if not with_metrics:
            return st
        # per-shard effective instance counts, gathered across every data
        # axis in order — rides the same replicated out_spec as the state
        shard_n = mask_.sum()[None]
        for ax in data_axes:
            shard_n = jax.lax.all_gather(shard_n, ax).reshape(-1)
        return st, DvmpMetrics(shard_n=shard_n, sweeps=st.sweep)

    return jax.jit(fit_shard)


@functools.lru_cache(maxsize=64)
def _sweep_program(cp: CompiledPlate, mesh: Mesh, data_axes: Tuple[str, ...],
                   backend: str, chunk: Optional[int]):
    dspec = P(data_axes)
    rep = P()

    @partial(
        shard_map, mesh=mesh,
        in_specs=(rep, rep, dspec, dspec, dspec), out_specs=(rep, rep),
        check_vma=False,
    )
    def body(prior_, post_, xc_, xd_, mask_):
        stats, _ = V.local_step(cp, post_, xc_, xd_, mask_,
                                backend=backend, chunk=chunk)
        stats = _psum_stats(stats, data_axes)
        new = V.global_update(prior_, stats)
        return new, V.elbo(cp, prior_, new, stats)

    return jax.jit(body)


def dvmp_fit(
    cp: CompiledPlate,
    prior: PlateParams,
    init: PlateParams,
    xc: jnp.ndarray,
    xd: jnp.ndarray,
    mesh: Mesh,
    data_axes: Tuple[str, ...] = ("data",),
    max_sweeps: int = 100,
    tol: float = 1e-4,
    mask: Optional[jnp.ndarray] = None,
    backend: str = "einsum",
    chunk: Optional[int] = None,
    with_metrics: bool = False,
) -> VMPState:
    """Distributed VMP fit.

    xc: [N, F], xd: [N, Fd] — N must divide by the product of data-axis sizes;
    use ``mask`` (same leading dim) to pad ragged global batches.
    Global params are replicated; data is sharded over ``data_axes``.
    Result is numerically identical to single-device ``vmp_fit`` on the
    concatenated data (up to float reduction order) — tested.

    ``with_metrics=True`` (part of the program-cache key — a separate
    compiled program, the metric-free path is untouched) also returns a
    :class:`DvmpMetrics`: per-shard effective instance counts (all_gather
    of each shard's mask sum — the data-balance gauge) and
    sweeps-to-convergence.
    """
    if mask is None:
        # laid out like the data: a default-device mask would pull every
        # shard's slice through one chip
        mask = jnp.ones(xc.shape[0], xc.dtype,
                        device=NamedSharding(mesh, P(tuple(data_axes))))
    prog = _fit_program(cp, mesh, tuple(data_axes), max_sweeps, tol,
                        backend, chunk, with_metrics)
    return prog(prior, init, xc, xd, mask)


@functools.lru_cache(maxsize=64)
def _posterior_z_program(cp: CompiledPlate, mesh: Mesh,
                         data_axes: Tuple[str, ...], backend: str,
                         chunk: Optional[int]):
    dspec = P(data_axes)
    rep = P()

    @partial(
        shard_map, mesh=mesh,
        in_specs=(rep, dspec, dspec), out_specs=dspec,
        check_vma=False,
    )
    def body(post_, xc_, xd_):
        mask = jnp.ones(xc_.shape[0], xc_.dtype)
        _, r = V.local_step(cp, post_, xc_, xd_, mask,
                            backend=backend, chunk=chunk)
        return r

    return jax.jit(body)


def dvmp_posterior_z(
    cp: CompiledPlate,
    post: PlateParams,
    xc: jnp.ndarray,
    xd: jnp.ndarray,
    mesh: Mesh,
    data_axes: Tuple[str, ...] = ("data",),
    backend: str = "einsum",
    chunk: Optional[int] = None,
) -> jnp.ndarray:
    """Replica-sharded q(Z | x) — the serving-tier query collective.

    Independent queries need NO cross-device reduction (unlike the fit
    path's suff-stat psum): the global posterior is replicated, the query
    batch is split over ``data_axes``, each replica answers its shard with
    ``local_step`` and the sharded result is reassembled.  Row results are
    identical to single-device :func:`repro.core.vmp.posterior_z`.
    ``xc.shape[0]`` must divide by the product of data-axis sizes (the
    serving tier pads buckets to a power of two, which does).
    """
    prog = _posterior_z_program(cp, mesh, tuple(data_axes), backend, chunk)
    return prog(post, xc, xd)


def dvmp_one_sweep(
    cp: CompiledPlate,
    prior: PlateParams,
    post: PlateParams,
    xc: jnp.ndarray,
    xd: jnp.ndarray,
    mask: jnp.ndarray,
    mesh: Mesh,
    data_axes: Tuple[str, ...] = ("data",),
    backend: str = "einsum",
    chunk: Optional[int] = None,
) -> Tuple[PlateParams, jnp.ndarray]:
    """Single distributed sweep — the building block reused by streaming VB
    (one sweep per arriving batch) and by the SVI driver."""
    prog = _sweep_program(cp, mesh, tuple(data_axes), backend, chunk)
    return prog(prior, post, xc, xd, mask)
