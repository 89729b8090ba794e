"""Pallas TPU kernels for batched log-space factor algebra (infer_exact).

The factor algebra of ``repro.infer_exact.factors`` flattens every table
over a discrete scope ``(v_1..v_k)`` to ``[B, M, N]`` where ``B`` is the
evidence-batch axis (many query instances propagate in ONE device call),
``N`` the product of the cardinalities being acted on (marginalized /
shared with the sepset / indexed by evidence) and ``M`` the product of the
remaining axes:

    log_product(a [B,M,N], b [B,N])   -> [B,M,N]   factor product (log add)
    log_marginalize(x [B,M,N])        -> [B,M]     stable logsumexp over N
    evidence_select(x [B,M,N], i [B]) -> [B,M]     per-instance evidence slice

``log_product`` and ``log_marginalize`` back the two message-passing hot
loops (sepset absorption, marginalization onto a sepset).
``evidence_select`` backs ``factors.reduce_evidence`` — the shrink-style
evidence reduction of the algebra layer; the default engine path folds
evidence as indicator factors instead, keeping clique shapes static per
evidence schema.

``log_marginalize`` uses the flash-attention style running-max/rescale
accumulation over N tiles so arbitrarily wide factors stream through VMEM.
All three tolerate ``-inf`` entries (structural zeros from evidence
indicators) without producing NaNs.

TPU layout: the last two dims of every block are either (8, 128)-aligned
or the full extent of the array's last two dims.  Per-row results
(``[B, M]``) therefore leave the kernels as ``[B, M, 1]`` columns, and the
per-batch operands (``b [B, N]``, ``idx [B]``) enter with a unit axis
(``[B, 1, N]``, ``[B, 1, 1]``); the wrappers restore the public shapes.
Compile/interpret policy: ``clg_stats._resolve_interpret``.

Oracles: ``repro.kernels.ref.{log_product_ref,log_marginalize_ref,
evidence_select_ref}``.  Jit'd public wrappers: ``repro.kernels.ops``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.clg_stats import _resolve_interpret

NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# log_product: a [B, M, N] + b [B, N] broadcast over M
# ---------------------------------------------------------------------------


def _product_kernel(a_ref, b_ref, o_ref):
    o_ref[0] = a_ref[0] + b_ref[0]                 # [bm, bn] + [1, bn]


def log_product(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 256,
                bn: int = 2048, interpret: Optional[bool] = None
                ) -> jnp.ndarray:
    """Log-space factor product of ``a`` with a sepset factor ``b``."""
    B, M, N = a.shape
    bm, bn = min(bm, M), min(bn, N)
    nm, nn = pl.cdiv(M, bm), pl.cdiv(N, bn)
    pad_m, pad_n = nm * bm - M, nn * bn - N
    b = b.astype(jnp.float32).reshape(B, 1, N)
    if pad_m or pad_n:
        a = jnp.pad(a, ((0, 0), (0, pad_m), (0, pad_n)))
        b = jnp.pad(b, ((0, 0), (0, 0), (0, pad_n)))
    out = pl.pallas_call(
        _product_kernel,
        grid=(B, nm, nn),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda b_, mi, ni: (b_, mi, ni)),
            pl.BlockSpec((1, 1, bn), lambda b_, mi, ni: (b_, 0, ni)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b_, mi, ni: (b_, mi, ni)),
        out_shape=jax.ShapeDtypeStruct((B, nm * bm, nn * bn), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(a.astype(jnp.float32), b)
    return out[:, :M, :N]


# ---------------------------------------------------------------------------
# log_marginalize: stable streaming logsumexp over the last axis
# ---------------------------------------------------------------------------


def _marginalize_kernel(x_ref, o_ref, m_scr, s_scr, *, nn: int):
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0].astype(jnp.float32)           # [bm, bn]
    m_prev = m_scr[...]                        # [bm, 1]
    m_new = jnp.maximum(m_prev, x.max(-1, keepdims=True))
    # safe center: where the running max is still -inf every exp() below is 0
    ms = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - ms), 0.0)
    s_scr[...] = s_scr[...] * corr + jnp.exp(x - ms).sum(-1, keepdims=True)
    m_scr[...] = m_new

    @pl.when(ni == nn - 1)
    def _final():
        s = s_scr[...]
        ms_f = jnp.where(jnp.isfinite(m_scr[...]), m_scr[...], 0.0)
        o_ref[0] = jnp.where(s > 0.0, ms_f + jnp.log(jnp.maximum(s, 1e-37)),
                             NEG_INF)


def log_marginalize(x: jnp.ndarray, *, bm: int = 256, bn: int = 256,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """logsumexp over the last axis of ``x [B, M, N]`` -> ``[B, M]``."""
    B, M, N = x.shape
    bm, bn = min(bm, M), min(bn, N)
    nm, nn = pl.cdiv(M, bm), pl.cdiv(N, bn)
    pad_m, pad_n = nm * bm - M, nn * bn - N
    if pad_m or pad_n:
        x = jnp.pad(x, ((0, 0), (0, pad_m), (0, pad_n)),
                    constant_values=NEG_INF)
    out = pl.pallas_call(
        functools.partial(_marginalize_kernel, nn=nn),
        grid=(B, nm, nn),
        in_specs=[pl.BlockSpec((1, bm, bn), lambda b_, mi, ni: (b_, mi, ni))],
        out_specs=pl.BlockSpec((1, bm, 1), lambda b_, mi, ni: (b_, mi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nm * bm, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
        ],
        interpret=_resolve_interpret(interpret),
    )(x.astype(jnp.float32))
    return out[:, :M, 0]


# ---------------------------------------------------------------------------
# evidence_select: per-batch-instance gather along the last axis
# ---------------------------------------------------------------------------


def _select_kernel(x_ref, i_ref, o_ref, *, bn: int):
    ni = pl.program_id(2)

    @pl.when(ni == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, NEG_INF)

    x = x_ref[0].astype(jnp.float32)           # [bm, bn]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + ni * bn
    o_ref[0] = jnp.maximum(
        o_ref[0], jnp.where(col == i_ref[0], x, NEG_INF).max(-1,
                                                             keepdims=True))


def evidence_select(x: jnp.ndarray, idx: jnp.ndarray, *, bm: int = 256,
                    bn: int = 2048, interpret: Optional[bool] = None
                    ) -> jnp.ndarray:
    """``x [B, M, N], idx [B] int`` -> ``[B, M]`` with ``out[b] = x[b,:,idx[b]]``.

    This is batched evidence reduction: each query instance clamps its own
    observed value, shrinking the factor by one axis in a single device call.
    """
    B, M, N = x.shape
    bm, bn = min(bm, M), min(bn, N)
    nm, nn = pl.cdiv(M, bm), pl.cdiv(N, bn)
    pad_m, pad_n = nm * bm - M, nn * bn - N
    if pad_m or pad_n:
        x = jnp.pad(x, ((0, 0), (0, pad_m), (0, pad_n)),
                    constant_values=NEG_INF)
    out = pl.pallas_call(
        functools.partial(_select_kernel, bn=bn),
        grid=(B, nm, nn),
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda b_, mi, ni: (b_, mi, ni)),
            pl.BlockSpec((1, 1, 1), lambda b_, mi, ni: (b_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, 1), lambda b_, mi, ni: (b_, mi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nm * bm, 1), jnp.float32),
        interpret=_resolve_interpret(interpret),
    )(x.astype(jnp.float32), idx.astype(jnp.int32).reshape(B, 1, 1))
    return out[:, :M, 0]


# ---------------------------------------------------------------------------
# cg_weak_marg: moment-matched weak marginal of a CG mixture
# ---------------------------------------------------------------------------


def _weak_marg_kernel(lw_ref, mu_ref, sg_ref, p_ref, mh_ref, sh_ref,
                      *, n: int):
    lw = lw_ref[0].astype(jnp.float32)              # [bm, N]
    m = lw.max(-1, keepdims=True)                   # [bm, 1]
    ms = jnp.where(jnp.isfinite(m), m, 0.0)
    w = jnp.where(jnp.isfinite(lw), jnp.exp(lw - ms), 0.0)
    s = w.sum(-1, keepdims=True)                    # [bm, 1]
    p_ref[0] = jnp.where(s > 0.0, ms + jnp.log(jnp.maximum(s, 1e-37)),
                         NEG_INF)
    wn = w / jnp.maximum(s, 1e-37)                  # [bm, N] normalized
    dead = s <= 0.0
    # moment i of the mixture and component covariance (i, j) are
    # leading-axis slices: the mixture axis N stays on the lanes
    mu = [mu_ref[0, i].astype(jnp.float32) for i in range(n)]   # [bm, N]
    mu_hat = [(wn * mu_i).sum(-1, keepdims=True) for mu_i in mu]
    for i in range(n):
        mh_ref[0, i] = jnp.where(dead, 0.0, mu_hat[i])
        for j in range(n):
            second = (wn * (sg_ref[0, i * n + j].astype(jnp.float32)
                            + mu[i] * mu[j])).sum(-1, keepdims=True)
            sh_ref[0, i * n + j] = jnp.where(
                dead, float(i == j), second - mu_hat[i] * mu_hat[j])


def cg_weak_marg(logw: jnp.ndarray, mu: jnp.ndarray, sigma: jnp.ndarray,
                 *, bm: int = 64, interpret: Optional[bool] = None
                 ) -> tuple:
    """Moment-matching weak marginal: collapse the mixture axis N.

    ``logw [B, M, N]``, ``mu [B, M, N, n]``, ``sigma [B, M, N, n, n]`` ->
    ``(logp [B, M], mu [B, M, n], sigma [B, M, n, n])`` where each (b, m)
    row becomes the single Gaussian matching the mixture's total mass,
    mean and covariance — the distribute-pass hot loop of the strong
    junction tree (Lauritzen 1992 weak marginals).  ``-inf`` weights
    (structural zeros from evidence indicators) are inert; fully dead rows
    yield ``(-inf, 0, I)``.  Oracle: ``repro.kernels.ref.cg_weak_marg_ref``.
    """
    B, M, N = logw.shape
    n = mu.shape[-1]
    bm = min(bm, M)
    nm = pl.cdiv(M, bm)
    pad_m = nm * bm - M
    if pad_m:
        logw = jnp.pad(logw, ((0, 0), (0, pad_m), (0, 0)),
                       constant_values=NEG_INF)
        mu = jnp.pad(mu, ((0, 0), (0, pad_m), (0, 0), (0, 0)))
        sigma = jnp.pad(sigma, ((0, 0), (0, pad_m), (0, 0), (0, 0), (0, 0)))
    Mp = nm * bm
    mu2 = jnp.moveaxis(mu, -1, 1)                              # [B, n, Mp, N]
    sg2 = jnp.moveaxis(sigma.reshape(B, Mp, N, n * n), -1, 1)  # [B, nn, Mp, N]
    p, mh, sh = pl.pallas_call(
        functools.partial(_weak_marg_kernel, n=n),
        grid=(B, nm),
        in_specs=[
            pl.BlockSpec((1, bm, N), lambda b_, mi: (b_, mi, 0)),
            pl.BlockSpec((1, n, bm, N), lambda b_, mi: (b_, 0, mi, 0)),
            pl.BlockSpec((1, n * n, bm, N), lambda b_, mi: (b_, 0, mi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, 1), lambda b_, mi: (b_, mi, 0)),
            pl.BlockSpec((1, n, bm, 1), lambda b_, mi: (b_, 0, mi, 0)),
            pl.BlockSpec((1, n * n, bm, 1), lambda b_, mi: (b_, 0, mi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n, Mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n * n, Mp, 1), jnp.float32),
        ],
        interpret=_resolve_interpret(interpret),
    )(logw.astype(jnp.float32), mu2.astype(jnp.float32),
      sg2.astype(jnp.float32))
    mh = jnp.moveaxis(mh[:, :, :M, 0], 1, -1)                  # [B, M, n]
    sh = jnp.moveaxis(sh[:, :, :M, 0], 1, -1).reshape(B, M, n, n)
    return p[:, :M, 0], mh, sh
