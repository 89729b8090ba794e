"""Jit'd public wrappers for the Pallas kernels.

Every kernel follows one compile/interpret policy
(``clg_stats._resolve_interpret``), asked when a wrapper is called or
traced — never at import, which would initialize the backend and take hold
of the chip in any process that imports this module: compiled natively
when the default jax backend is a TPU, interpret mode (python semantics of
the same kernel body) elsewhere.
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels.clg_stats import (_resolve_interpret,
                                     clg_disc_counts as _clg_disc,
                                     clg_suffstats as _clg,
                                     clg_suffstats_latent as _clg_latent)
from repro.kernels.family_counts import family_counts as _famcounts
from repro.kernels.factor_ops import (cg_weak_marg as _cgweak,
                                      evidence_select as _evsel,
                                      log_marginalize as _logmarg,
                                      log_product as _logprod)
from repro.kernels.flash_attn import flash_attention as _flash
from repro.kernels.ssd_scan import ssd_scan as _ssd


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal=True, window=None, bq=128, bk=128):
    return _flash(q, k, v, causal=causal, window=window, bq=bq, bk=bk,
                  interpret=_resolve_interpret(None))


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B, C, chunk=128):
    return _ssd(x, dt, A, B, C, chunk, interpret=_resolve_interpret(None))


@partial(jax.jit, static_argnames=("block",))
def clg_suffstats(d, y, r, *, block=2048):
    return _clg(d, y, r, block=block)


@partial(jax.jit, static_argnames=("block",))
def clg_seq_suffstats(d, y, r, *, block=2048):
    """Sequence-batch CLG suff-stats: flattens the ``[B, T]`` leading dims
    of ``d [B,T,F,D] / y [B,T,F] / r [B,T,K]`` into the kernel's instance
    axis and dispatches one ``clg_suffstats`` call — the temporal
    (``pgm_models.dynamic``) entry to the same pallas/interpret kernel the
    static plate uses.  Masking is the caller's job: zero ``r`` rows
    contribute nothing."""
    B, T = r.shape[0], r.shape[1]
    return _clg(d.reshape(B * T, *d.shape[2:]), y.reshape(B * T, *y.shape[2:]),
                r.reshape(B * T, r.shape[2]), block=block)


@partial(jax.jit, static_argnames=("block",))
def clg_suffstats_latent(obs, h_mean, y, r, s_hh, *, block=2048):
    return _clg_latent(obs, h_mean, y, r, s_hh, block=block)


@partial(jax.jit, static_argnames=("C", "block"))
def clg_disc_counts(xd, r, C, *, block=2048):
    return _clg_disc(xd, r, C, block=block)


@partial(jax.jit, static_argnames=("C", "block"))
def family_counts(xd, strides, w, C, *, block=2048):
    return _famcounts(xd, strides, w, C, block=block)


@partial(jax.jit, static_argnames=("bm", "bn"))
def log_product(a, b, *, bm=256, bn=2048):
    return _logprod(a, b, bm=bm, bn=bn)


@partial(jax.jit, static_argnames=("bm", "bn"))
def log_marginalize(x, *, bm=256, bn=256):
    return _logmarg(x, bm=bm, bn=bn)


@partial(jax.jit, static_argnames=("bm", "bn"))
def evidence_select(x, idx, *, bm=256, bn=2048):
    return _evsel(x, idx, bm=bm, bn=bn)


@partial(jax.jit, static_argnames=("bm",))
def cg_weak_marg(logw, mu, sigma, *, bm=64):
    return _cgweak(logw, mu, sigma, bm=bm)
