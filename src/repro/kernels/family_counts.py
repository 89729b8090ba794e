"""Pallas TPU kernel for batched family-count reduction (structure learning).

Score-based structure search (``repro.learn_structure``) is dominated by
counting: every candidate family (child, parent set) needs the joint-
configuration counts

    counts[m, c] = sum_n w[n] [ code(x[n], family m) == c ]

where ``code`` is the mixed-radix flattening of the family's (child,
parents) columns.  Because the radix weights are per-family constants, the
code of instance n under family m is a plain dot product

    code[m, n] = sum_f strides[m, f] * xd[n, f]

(``strides[m, f] = 0`` for columns outside the family), so ONE pass over
the instances scores every candidate family at once: the grid is the
instance blocks alone (sequential), the columns arrive instance-minor
(``[Fd, block]``: instances on the 128-lane axis), each block forms the
``[M, block]`` code matrix and, per family, adds the weighted one-hot
histogram ``w [1, block] x onehot [C, block]^T`` on the MXU into the
resident ``[M, C]`` output.

Same compile/interpret policy as the other kernels
(``clg_stats._resolve_interpret``).  Oracle: ``repro.kernels.ref.
family_counts_ref``; jit'd wrapper: ``repro.kernels.ops.family_counts``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.clg_stats import _resolve_interpret


def _kernel(x_ref, s_ref, w_ref, out_ref, code_scr, *, Fd: int, C: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # mixed-radix flat configuration code of every instance under every
    # family: integer-valued floats, exact well past any practical config
    # count (an f32 VPU multiply-add per column, no MXU rounding)
    code = sum(s_ref[f].astype(jnp.float32)                    # [M, 1]
               * x_ref[pl.ds(f, 1), :].astype(jnp.float32)     # [1, bn]
               for f in range(Fd))                             # [M, bn]
    code_scr[...] = code
    w = w_ref[...].astype(jnp.float32)                         # [1, bn]
    configs = jax.lax.broadcasted_iota(jnp.int32, (C, code.shape[1]),
                                       0).astype(jnp.float32)

    def family(m, carry):
        onehot = (configs == code_scr[pl.ds(m, 1), :]).astype(jnp.float32)
        out_ref[pl.ds(m, 1), :] += jax.lax.dot_general(
            w, onehot, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)                # [1, C]
        return carry

    jax.lax.fori_loop(0, code.shape[0], family, 0)


def family_counts(xd: jnp.ndarray, strides: jnp.ndarray, w: jnp.ndarray,
                  C: int, *, block: int = 2048,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """xd: [N, Fd] int discrete columns; strides: [M, Fd] mixed-radix
    weights (0 outside the family); w: [N] instance weights/mask.

    Returns counts [M, C] — the weighted joint-configuration histogram of
    every candidate family in one pass over the instances.  Configurations
    beyond a family's true size (its code range is a prefix of [0, C)) stay
    exactly zero (oracle: kernels.ref.family_counts_ref).
    """
    interpret = _resolve_interpret(interpret)
    N, Fd = xd.shape
    M = strides.shape[0]
    block = min(block, N)
    nb = pl.cdiv(N, block)
    pad = nb * block - N
    xt = xd.astype(jnp.int32).T                                # [Fd, N]
    w = w.reshape(1, N)
    if pad:
        # padded instances carry w = 0: their (valid) code 0 adds nothing
        xt = jnp.pad(xt, ((0, 0), (0, pad)))
        w = jnp.pad(w, ((0, 0), (0, pad)))
    # [Fd, M, 1]: column f of the strides is a leading-axis slice in-kernel
    s = strides.astype(jnp.int32).T[:, :, None]

    return pl.pallas_call(
        functools.partial(_kernel, Fd=Fd, C=C),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((Fd, block), lambda bi: (0, bi)),
            pl.BlockSpec((Fd, M, 1), lambda bi: (0, 0, 0)),
            pl.BlockSpec((1, block), lambda bi: (0, bi)),
        ],
        out_specs=pl.BlockSpec((M, C), lambda bi: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((M, block), jnp.float32)],
        interpret=interpret,
    )(xt, s, w)
