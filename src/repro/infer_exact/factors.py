"""Batched log-space factor algebra over discrete variables.

A :class:`Factor` is a named-scope log-probability table with an optional
leading batch axis (one slice per evidence instance — the whole junction
tree propagates B queries in one device call).  Scopes and cardinalities are
static Python; tables are jnp arrays, so every operation traces cleanly
under ``jax.jit`` / ``jax.vmap``.

The two hot loops of junction-tree propagation — sepset absorption (factor
product against a message) and marginalization onto a sepset — dispatch to
the Pallas kernels in ``repro.kernels.factor_ops`` when ``use_pallas`` is
on; the default is the pure-jnp path (identical semantics, and the kernels
are verified against it in tests/test_kernels.py).
"""

from __future__ import annotations

import math
import os
from typing import Dict, NamedTuple, Sequence, Tuple

import jax.numpy as jnp
import jax.scipy.special as jsp

# Flip on to route marginalize/absorb through the Pallas kernels
# (compiled on a TPU, interpret mode elsewhere: kernels.clg_stats policy).
USE_PALLAS = os.environ.get("REPRO_EXACT_PALLAS", "0") == "1"

NEG_INF = float("-inf")


class Factor(NamedTuple):
    """log p over ``scope``; table shape = batch_shape + cards."""

    scope: Tuple[str, ...]
    cards: Tuple[int, ...]
    logp: jnp.ndarray

    @property
    def batch_ndim(self) -> int:
        return self.logp.ndim - len(self.scope)


def _expand(f: Factor, scope: Tuple[str, ...], cards: Tuple[int, ...]
            ) -> jnp.ndarray:
    """Broadcast ``f.logp`` onto the superset ``scope`` (batch axes lead)."""
    nb = f.batch_ndim
    pos = {v: i for i, v in enumerate(f.scope)}
    order = sorted(range(len(f.scope)), key=lambda i: scope.index(f.scope[i]))
    t = jnp.transpose(f.logp, tuple(range(nb)) + tuple(nb + i for i in order))
    for axis, v in enumerate(scope):
        if v not in pos:
            t = jnp.expand_dims(t, nb + axis)
    return t


def product(factors: Sequence[Factor]) -> Factor:
    """Log-space factor product: union scope, broadcast add."""
    scope: Tuple[str, ...] = ()
    card_of: Dict[str, int] = {}
    for f in factors:
        for v, c in zip(f.scope, f.cards):
            if v not in card_of:
                scope = scope + (v,)
                card_of[v] = c
            elif card_of[v] != c:
                raise ValueError(f"cardinality clash for {v}")
    cards = tuple(card_of[v] for v in scope)
    t = _expand(factors[0], scope, cards)
    for f in factors[1:]:
        t = t + _expand(f, scope, cards)
    return Factor(scope, cards, t)


def absorb(f: Factor, msg: Factor, *, use_pallas: bool = False) -> Factor:
    """``f * msg`` where ``msg.scope`` is a subset of ``f.scope``.

    This is the sepset-absorption hot loop; with ``use_pallas`` the tables
    are flattened to [B, M, N] (sepset vars minor) and the add runs in the
    ``log_product`` kernel.
    """
    if not set(msg.scope) <= set(f.scope):
        return product([f, msg])
    if not use_pallas or f.batch_ndim != 1 or msg.batch_ndim != 1:
        return product([f, msg])
    from repro.kernels import ops

    sep = msg.scope
    keep = tuple(v for v in f.scope if v not in sep)
    perm_scope = keep + sep
    ft = _permute(f, perm_scope)
    B = ft.shape[0]
    m = math.prod(f.cards[f.scope.index(v)] for v in keep)
    n = math.prod(msg.cards)
    mt = _permute(msg, sep)
    out = ops.log_product(ft.reshape(B, m, n), mt.reshape(B, n))
    cards = tuple(f.cards[f.scope.index(v)] for v in perm_scope)
    return Factor(perm_scope, cards, out.reshape((B,) + cards))


def _permute(f: Factor, scope: Tuple[str, ...]) -> jnp.ndarray:
    """Reorder ``f``'s table axes to match ``scope`` (same variable set)."""
    nb = f.batch_ndim
    perm = tuple(nb + f.scope.index(v) for v in scope)
    return jnp.transpose(f.logp, tuple(range(nb)) + perm)


def marginalize(f: Factor, keep: Sequence[str], *,
                use_pallas: bool = False) -> Factor:
    """logsumexp out every variable not in ``keep``."""
    keep = tuple(v for v in f.scope if v in set(keep))
    drop = tuple(v for v in f.scope if v not in set(keep))
    if not drop:
        return Factor(keep, tuple(f.cards[f.scope.index(v)] for v in keep),
                      _permute(f, keep))
    cards_keep = tuple(f.cards[f.scope.index(v)] for v in keep)
    t = _permute(f, keep + drop)
    if use_pallas and f.batch_ndim == 1:
        from repro.kernels import ops

        B = t.shape[0]
        m = math.prod(cards_keep)
        n = math.prod(f.cards[f.scope.index(v)] for v in drop)
        out = ops.log_marginalize(t.reshape(B, m, n))
        return Factor(keep, cards_keep, out.reshape((B,) + cards_keep))
    nb = f.batch_ndim
    axes = tuple(range(nb + len(keep), nb + len(f.scope)))
    return Factor(keep, cards_keep, jsp.logsumexp(t, axis=axes))


def reduce_evidence(f: Factor, var: str, idx: jnp.ndarray, *,
                    use_pallas: bool = False) -> Factor:
    """Clamp ``var`` to per-instance values ``idx`` ([B] int), dropping it.

    Shrink-style evidence reduction: the observed axis disappears, so
    downstream messages are smaller.  ``JunctionTreeEngine`` folds evidence
    as :func:`indicator` factors instead (static clique shapes per evidence
    schema); this op is the algebra layer's alternative for callers that
    want the smaller tables.
    """
    keep = tuple(v for v in f.scope if v != var)
    cards_keep = tuple(f.cards[f.scope.index(v)] for v in keep)
    t = _permute(f, keep + (var,))
    nb = f.batch_ndim
    if nb == 0:
        t = t[None]
        idx = jnp.asarray(idx).reshape(1)
        nb = 1
    B = t.shape[0]
    n = f.cards[f.scope.index(var)]
    flat = t.reshape(B, math.prod(cards_keep), n)
    if use_pallas:
        from repro.kernels import ops

        out = ops.evidence_select(flat, idx)
    else:
        out = jnp.take_along_axis(
            flat, idx.astype(jnp.int32)[:, None, None], axis=-1)[..., 0]
    out = out.reshape((B,) + cards_keep)
    if f.batch_ndim == 0:
        out = out[0]
    return Factor(keep, cards_keep, out)


def indicator(var: str, card: int, idx: jnp.ndarray) -> Factor:
    """log 1[x_var == idx] as a batched factor ([B] -> [B, card])."""
    idx = jnp.asarray(idx, jnp.int32).reshape(-1)
    onehot = idx[:, None] == jnp.arange(card)[None, :]
    return Factor((var,), (card,), jnp.where(onehot, 0.0, NEG_INF))


def normalize(f: Factor) -> Factor:
    """Normalize over scope axes (per batch instance)."""
    nb = f.batch_ndim
    axes = tuple(range(nb, f.logp.ndim))
    z = jsp.logsumexp(f.logp, axis=axes, keepdims=True)
    return Factor(f.scope, f.cards, f.logp - z)
