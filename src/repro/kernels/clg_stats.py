"""Pallas TPU kernels for the VMP E-step hot loop: CLG expected suff stats.

This is the paper's own compute kernel (DESIGN.md §6): for every continuous
leaf f and mixture component k, d-VMP reduces over (potentially millions
of) instances

    sxx[f,k] = sum_n r[n,k] d[n,f,:] d[n,f,:]^T      [D, D]
    sxy[f,k] = sum_n r[n,k] d[n,f,:] y[n,f]          [D]
    syy[f,k] = sum_n r[n,k] y[n,f]^2                 []

and, for every discrete leaf and component, the one-hot count reduction

    disc[f,k,c] = sum_n r[n,k] [x[n,f] == c]         [C]

TPU mapping: every reduction is one weighted Gram matrix
``G = A B^T`` over the instance axis.  Inputs are laid out instance-minor
(``[rows, N]``: the instance axis is the 128-lane axis, the few features /
components / design dims are sublane rows, each block covers all of them),
so every BlockSpec is ``(all rows, block)`` and satisfies the TPU's
(8, 128) tiling rule for any feature count.  The grid is the instance
blocks alone (sequential); per block the kernel writes the rows of ``A``
(responsibility-weighted) and ``B`` (design products) into VMEM scratch
and accumulates ``A B^T`` — one MXU matmul contracting the instance block —
into the resident output.  The wrappers slice the few-hundred-element Gram
matrix into the suff-stat pytree.  The per-shard result is the psum
payload of dvmp (one message pytree per sweep).

``interpret=None`` (the default) resolves at call time through
:func:`_resolve_interpret`, the one compile/interpret policy every kernel
of this package follows.

Oracles: ``repro.kernels.ref.{clg_suffstats_ref,clg_suffstats_latent_ref,
clg_disc_counts_ref}``.
"""

from __future__ import annotations

import functools
import os
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Row = jnp.ndarray                    # one [1, block] float32 row
RowFn = Callable[[Callable[[int, int], Row]], List[Row]]


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """Compiled on TPU (or forced via REPRO_PALLAS_COMPILE=1); interpret
    elsewhere — CPU Pallas has no Mosaic lowering for these kernels.
    ``REPRO_PALLAS_INTERPRET=1`` forces interpret mode everywhere (wins
    over COMPILE): the CI parity leg runs the kernel suite once under each
    policy so the TPU-compiled path cannot silently diverge from the
    interpret semantics the CPU container tests.  Asked at call time, never
    at import: asking initializes the backend, which takes hold of the
    chip."""
    if interpret is not None:
        return interpret
    if os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1":
        return True
    if os.environ.get("REPRO_PALLAS_COMPILE", "0") == "1":
        return False
    return jax.default_backend() != "tpu"


def _round8(n: int) -> int:
    return -(-n // 8) * 8


def _gram_kernel(*refs, n_in: int, lhs: RowFn, rhs: RowFn):
    in_refs, out_ref = refs[:n_in], refs[n_in]
    a_scr, b_scr = refs[n_in + 1:]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        # rows past the recipe stay zero for the whole grid
        a_scr[...] = jnp.zeros_like(a_scr)
        b_scr[...] = jnp.zeros_like(b_scr)

    def row(i: int, j: int) -> Row:
        return in_refs[i][pl.ds(j, 1), :].astype(jnp.float32)

    for i, v in enumerate(lhs(row)):
        a_scr[pl.ds(i, 1), :] = v
    for j, v in enumerate(rhs(row)):
        b_scr[pl.ds(j, 1), :] = v
    out_ref[...] += jax.lax.dot_general(
        a_scr[...], b_scr[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _gram(inputs: Sequence[jnp.ndarray], pad_values: Sequence[float],
          lhs: RowFn, n_lhs: int, rhs: RowFn, n_rhs: int, *, block: int,
          interpret: Optional[bool]) -> jnp.ndarray:
    """``G[i, j] = sum_n lhs_i[n] * rhs_j[n]`` -> ``[n_lhs, n_rhs]``.

    ``inputs`` are instance-minor ``[rows, N]`` arrays; ``lhs``/``rhs``
    build their rows from ``row(input, row_index)`` loads of one instance
    block (static recipes: python loops unrolled at trace time).  Padded
    instances take ``pad_values`` per input; recipes make them contribute
    nothing (every lhs row carries a responsibility, padded with 0).
    """
    interpret = _resolve_interpret(interpret)
    N = inputs[0].shape[1]
    block = min(block, N)
    nb = pl.cdiv(N, block)
    pad = nb * block - N
    if pad:
        inputs = [jnp.pad(x, ((0, 0), (0, pad)), constant_values=v)
                  for x, v in zip(inputs, pad_values)]
    na, nr = _round8(n_lhs), _round8(n_rhs)
    out = pl.pallas_call(
        functools.partial(_gram_kernel, n_in=len(inputs), lhs=lhs, rhs=rhs),
        grid=(nb,),
        in_specs=[pl.BlockSpec((x.shape[0], block), lambda bi: (0, bi))
                  for x in inputs],
        out_specs=pl.BlockSpec((na, nr), lambda bi: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((na, nr), jnp.float32),
        scratch_shapes=[pltpu.VMEM((na, block), jnp.float32),
                        pltpu.VMEM((nr, block), jnp.float32)],
        interpret=interpret,
    )(*inputs)
    return out[:n_lhs, :n_rhs]


def _minor(x: jnp.ndarray) -> jnp.ndarray:
    """[N, ...] -> [prod(...), N]: instance axis minor (the lane axis)."""
    return x.reshape(x.shape[0], -1).T


def _clg_gram(obs, y, r, h_mean, block, interpret):
    """Gram of the CLG reduction.  Columns, per leaf f (width W):
    ``o_a o_b`` (Do^2), ``o_a y`` (Do), ``y^2``, then for latent plates
    ``o_a`` (Do) and ``y``; latent plates append the ``E[h]`` rows (K*L)
    and a ones row.  Rows: ``r_k`` (K), then ``r_k E[h_kl]`` (K*L)."""
    _, F, Do = obs.shape
    K = r.shape[1]
    L = 0 if h_mean is None else h_mean.shape[2]
    inputs = [_minor(obs), _minor(y), _minor(r)]
    if L:
        inputs.append(_minor(h_mean))                      # [K*L, N]
    O, Y, R, H = 0, 1, 2, 3

    def lhs(row):
        rows = [row(R, k) for k in range(K)]
        rows += [row(R, k) * row(H, k * L + l)
                 for k in range(K) for l in range(L)]
        return rows

    def rhs(row):
        rows = []
        for f in range(F):
            o = [row(O, f * Do + a) for a in range(Do)]
            yf = row(Y, f)
            rows += [oa * ob for oa in o for ob in o]
            rows += [oa * yf for oa in o]
            rows.append(yf * yf)
            if L:
                rows += o + [yf]
        if L:
            rows += [row(H, i) for i in range(K * L)]
            rows.append(jnp.ones_like(row(Y, 0)))
        return rows

    W = Do * Do + Do + 1 + (Do + 1 if L else 0)
    n_rhs = F * W + (K * L + 1 if L else 0)
    G = _gram(inputs, [0.0] * len(inputs), lhs, K * (1 + L), rhs, n_rhs,
              block=block, interpret=interpret)
    leaf = G[:, :F * W].reshape(K * (1 + L), F, W).transpose(1, 0, 2)
    return leaf, G[:, F * W:]                 # [F, K(1+L), W], [K(1+L), .]


def clg_suffstats(d: jnp.ndarray, y: jnp.ndarray, r: jnp.ndarray, *,
                  block: int = 2048, interpret: Optional[bool] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """d: [N, F, D] design vectors; y: [N, F]; r: [N, K] responsibilities.

    Returns (sxx [F, K, D, D], sxy [F, K, D], syy [F, K]) — the RegSuffStats
    triple of repro.core.expfam (oracle: kernels.ref.clg_suffstats_ref).
    """
    F, D = d.shape[1], d.shape[2]
    K = r.shape[1]
    leaf, _ = _clg_gram(d, y, r, None, block, interpret)   # [F, K, W]
    sxx = leaf[..., :D * D].reshape(F, K, D, D)
    sxy = leaf[..., D * D:D * D + D]
    return sxx, sxy, leaf[..., D * D + D]


def clg_suffstats_latent(obs: jnp.ndarray, h_mean: jnp.ndarray,
                         y: jnp.ndarray, r: jnp.ndarray, s_hh: jnp.ndarray, *,
                         block: int = 2048, interpret: Optional[bool] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused latent-plate (FA/PPCA) suff-stats: component-major designs.

    obs: [N, F, Do] observed design vectors; h_mean: [N, K, L] per-component
    posterior means E[h | z=k]; y: [N, F]; r: [N, K]; s_hh: [K, L, L] the
    shared posterior covariance S_k of q(H | z=k) (so
    E[hh^T | z=k] = S_k + E[h]E[h]^T).

    Returns the FULL regression-moment triple over the concatenated design
    d[n,f,k] = [obs[n,f], E[h|z=k]] with the E[hh^T] covariance correction
    folded into the latent-latent block:

        sxx [F, K, D, D], sxy [F, K, D], syy [F, K],  D = Do + L

    One pass over instances; nothing [N, K, L, L]-shaped is ever formed
    (oracle: kernels.ref.clg_suffstats_latent_ref).
    """
    F, Do = obs.shape[1], obs.shape[2]
    K, L = h_mean.shape[1], h_mean.shape[2]
    leaf, tail = _clg_gram(obs, y, r, h_mean, block, interpret)
    rk, rh = leaf[:, :K], leaf[:, K:].reshape(F, K, L, -1)
    s_oo = rk[..., :Do * Do].reshape(F, K, Do, Do)
    sxy_o = rk[..., Do * Do:Do * Do + Do]
    syy = rk[..., Do * Do + Do]
    o_at = Do * Do + Do + 1                   # start of the o_a, y columns
    s_ho = rh[..., o_at:o_at + Do]                          # [F, K, L, Do]
    sxy_h = rh[..., o_at + Do]                              # [F, K, L]
    hh = tail[K:, :K * L].reshape(K, L, K, L)
    hh = jnp.einsum("klkm->klm", hh)                        # k = k' blocks
    hh = hh + tail[:K, K * L][:, None, None] * s_hh.astype(jnp.float32)
    hh = jnp.broadcast_to(hh[None], (F, K, L, L))
    top = jnp.concatenate([s_oo, jnp.swapaxes(s_ho, -1, -2)], axis=-1)
    bot = jnp.concatenate([s_ho, hh], axis=-1)
    sxx = jnp.concatenate([top, bot], axis=-2)
    return sxx, jnp.concatenate([sxy_o, sxy_h], axis=-1), syy


def clg_disc_counts(xd: jnp.ndarray, r: jnp.ndarray, C: int, *,
                    block: int = 2048, interpret: Optional[bool] = None
                    ) -> jnp.ndarray:
    """xd: [N, Fd] int discrete leaves; r: [N, K] responsibilities.

    Returns disc [Fd, K, C] — the weighted one-hot reduction
    ``sum_n r[n,k] onehot(xd[n,f], C)`` that completes the d-VMP message
    pytree (oracle: kernels.ref.clg_disc_counts_ref).  Same Gram kernel as
    :func:`clg_suffstats`, with indicator rows ``[x_f == c]`` on the right.
    """
    Fd = xd.shape[1]
    K = r.shape[1]

    def lhs(row):
        return [row(1, k) for k in range(K)]

    def rhs(row):
        return [(row(0, f) == c).astype(jnp.float32)
                for f in range(Fd) for c in range(C)]

    # padded instances get category -1: they match no indicator row
    G = _gram([_minor(xd.astype(jnp.int32)), _minor(r)], [-1, 0.0],
              lhs, K, rhs, Fd * C, block=block, interpret=interpret)
    return G.reshape(K, Fd, C).transpose(1, 0, 2)
