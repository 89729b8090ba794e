"""GQA/MQA attention with causal + sliding-window masking.

Three execution paths:

* ``attention_reference`` — O(S^2)-memory jnp oracle (tests, tiny shapes).
* ``attention_blockwise`` — lax.scan over KV blocks with a running-softmax
  accumulator (flash-attention recurrence in XLA).  This is what large
  shapes compile through: peak memory O(S * block) instead of O(S^2), which
  is what lets prefill_32k lower within HBM.  The Pallas kernel
  (``repro.kernels.flash_attn``) implements the same recurrence with
  explicit VMEM tiling for the TPU target; interpret-mode tests pin all
  three paths together.
* ``attention_decode`` — one query token against a KV cache (serve_step).

All paths take q:[B,S,Hq,D], k/v:[B,S,Hkv,D] and return [B,S,Hq,D];
GQA folds q-head groups onto kv heads via reshape (no materialized repeat).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _fold_gqa(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """[B,S,Hq,D] -> [B,S,G,Hkv,D] with G = Hq // Hkv (G-MAJOR fold).

    G-major (q head h uses kv head h % Hkv) so that a contiguous 'model'
    sharding of the fused Hq dim lands on the G dim after the reshape —
    that keeps GQA tensor-parallel even when Hkv < mesh model size."""
    B, S, Hq, D = q.shape
    return q.reshape(B, S, Hq // n_kv, n_kv, D)


def _mask_bias(sq: int, sk: int, q_offset, causal: bool,
               window: Optional[int]) -> jnp.ndarray:
    """[sq, sk] additive mask; q position i is q_offset + i."""
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    ok = jnp.ones((sq, sk), jnp.bool_)
    if causal:
        ok = ok & (kpos <= qpos)
    if window is not None:
        ok = ok & (kpos > qpos - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# reference (quadratic memory)
# ---------------------------------------------------------------------------


def attention_reference(q, k, v, *, causal=True, window=None, q_offset=0,
                        scale=None):
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    scale = scale or 1.0 / math.sqrt(D)
    qg = _fold_gqa(q, Hkv)                                  # [B,Sq,G,Hkv,D]
    logits = jnp.einsum("bqghd,bkhd->bghqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = logits + _mask_bias(Sq, Sk, q_offset, causal, window)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bghqk,bkhd->bqghd", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# blockwise (flash recurrence in XLA) — the production path
# ---------------------------------------------------------------------------


def attention_blockwise(q, k, v, *, causal=True, window=None, q_offset=0,
                        scale=None, kv_block: int = 1024):
    """Streaming-softmax attention: scan over KV blocks.

    Equivalent to the reference up to fp assoc.; peak memory O(Sq * kv_block).
    """
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    scale = scale or 1.0 / math.sqrt(D)
    kv_block = min(kv_block, Sk)
    nblk = (Sk + kv_block - 1) // kv_block
    pad = nblk * kv_block - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # operands stay bf16 (f32 upcasts of big K/V get hoisted out of the
    # scan by XLA and double HBM traffic — see EXPERIMENTS.md §Perf);
    # accumulation is f32 via preferred_element_type.
    qg = (_fold_gqa(q, Hkv) * jnp.asarray(scale, q.dtype))
    kb = k.reshape(B, nblk, kv_block, Hkv, D)
    vb = v.reshape(B, nblk, kv_block, Hkv, D)
    kb = jnp.moveaxis(kb, 1, 0)                             # [nblk,B,kb,Hkv,D]
    vb = jnp.moveaxis(vb, 1, 0)

    qpos = q_offset + jnp.arange(Sq)

    def step(carry, inp):
        m, l, acc = carry                                   # running max/sum/out
        kblk, vblk, blk_idx = inp
        kpos = blk_idx * kv_block + jnp.arange(kv_block)
        logits = jnp.einsum("bqghd,bkhd->bqghk", qg, kblk,
                            preferred_element_type=jnp.float32)
        ok = kpos[None, :] < Sk                             # mask padding
        if causal:
            ok = ok & (kpos[None, :] <= qpos[:, None])
        if window is not None:
            ok = ok & (kpos[None, :] > qpos[:, None] - window)
        logits = logits + jnp.where(ok, 0.0, NEG_INF)[None, :, None, None, :]
        m_new = jnp.maximum(m, logits.max(-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqghk,bkhd->bqghd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    G = Hq // Hkv
    m0 = jnp.full((B, Sq, G, Hkv), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, G, Hkv), jnp.float32)
    a0 = jnp.zeros((B, Sq, G, Hkv, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# decode: one token vs KV cache
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: jnp.ndarray        # [B, C, Hkv, D]  (C = cache capacity; ring for SWA)
    v: jnp.ndarray        # [B, C, Hkv, D]
    length: jnp.ndarray   # [] int32 — tokens written so far (absolute)


def init_kv_cache(batch: int, capacity: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
        length=jnp.zeros((), jnp.int32),
    )


def cache_update(cache: KVCache, k_new: jnp.ndarray, v_new: jnp.ndarray
                 ) -> KVCache:
    """Append one token (ring-buffer write: pos = length mod capacity)."""
    C = cache.k.shape[1]
    pos = jnp.mod(cache.length, C)
    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, pos, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, pos, axis=1)
    return KVCache(k=k, v=v, length=cache.length + 1)


def attention_decode(q, cache: KVCache, *, window=None, scale=None):
    """q: [B, 1, Hq, D] vs ring-buffer cache. Returns [B, 1, Hq, D].

    Ring semantics: slot s holds absolute position p(s) = s + C*floor(...)
    — we reconstruct each slot's absolute position from ``length`` and mask
    slots that are empty or outside the sliding window.
    """
    B, _, Hq, D = q.shape
    C, Hkv = cache.k.shape[1], cache.k.shape[2]
    scale = scale or 1.0 / math.sqrt(D)
    qg = _fold_gqa(q, Hkv) * jnp.asarray(scale, q.dtype)    # [B,1,G,Hkv,D]
    logits = jnp.einsum("bqghd,bkhd->bqghk", qg.astype(cache.k.dtype),
                        cache.k, preferred_element_type=jnp.float32)
    # absolute position of each slot given length L (slots wrap mod C)
    L = cache.length                                        # tokens written
    slots = jnp.arange(C)
    wraps = (L - 1 - slots) // C                            # how many writes ago
    abs_pos = slots + wraps * C                             # latest abs pos in slot
    valid = (abs_pos >= 0) & (abs_pos < L)
    if window is not None:
        valid = valid & (abs_pos > L - 1 - window)
    logits = logits + jnp.where(valid, 0.0, NEG_INF)[None, None, None, None, :]
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bqghk,bkhd->bqghd", w.astype(cache.v.dtype), cache.v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# context-parallel decode: KV cache sharded over the 'model' axis (seq dim)
# ---------------------------------------------------------------------------
#
# For GQA models with few KV heads (glm4: 2) a 32k decode cache cannot shard
# over heads; the production layout shards the cache SEQUENCE over 'model'
# (flash-decode / context parallelism): every model shard scores q against
# its cache slice, then the partial softmax accumulators are combined with
# one pmax + two psums of [B, H, G]-sized scalars — collective bytes are
# tiny compared to the HBM reads the shard saved (DESIGN.md §5).


def _decode_partial(q, k, v, abs_pos, length, window, scale):
    """Local flash-decode accumulators. q: [B,1,Hq,D]; k/v: [B,C_loc,Hkv,D];
    abs_pos: [C_loc] absolute position each local slot holds (-1 = empty)."""
    B, _, Hq, D = q.shape
    Hkv = k.shape[2]
    qg = _fold_gqa(q, Hkv) * jnp.asarray(scale, q.dtype)
    logits = jnp.einsum("bqghd,bkhd->bqghk", qg.astype(k.dtype), k,
                        preferred_element_type=jnp.float32)
    valid = (abs_pos >= 0) & (abs_pos < length)
    if window is not None:
        valid = valid & (abs_pos > length - 1 - window)
    logits = logits + jnp.where(valid, 0.0, NEG_INF)[None, None, None, None, :]
    m = logits.max(-1)                                        # [B,1,G,Hkv]
    p = jnp.exp(logits - m[..., None])
    l = p.sum(-1)
    acc = jnp.einsum("bqghk,bkhd->bqghd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def attention_decode_ctx_parallel(q, cache: KVCache, mesh, *,
                                  model_axis="model", data_axes=("data",),
                                  window=None, scale=None):
    """Decode with the cache's seq dim sharded over ``model_axis``.

    q is replicated over 'model'; output is replicated over 'model'.
    """
    from functools import partial as _partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    B, _, Hq, D = q.shape
    C = cache.k.shape[1]
    scale_ = scale or 1.0 / math.sqrt(D)
    s = mesh.shape[model_axis]
    C_loc = C // s
    ndata = 1
    for a in data_axes:
        ndata *= mesh.shape[a]
    dp = data_axes if B % ndata == 0 else ()   # tiny batches stay replicated

    @_partial(
        shard_map, mesh=mesh,
        in_specs=(P(dp, None, None, None),
                  P(dp, model_axis, None, None),
                  P(dp, model_axis, None, None),
                  P()),
        out_specs=P(dp, None, None, None),
        check_vma=False,
    )
    def body(q_, k_, v_, length):
        j = jax.lax.axis_index(model_axis)
        slots = j * C_loc + jnp.arange(C_loc)      # global slot ids
        wraps = (length - 1 - slots) // C
        abs_pos = slots + wraps * C
        m, l, acc = _decode_partial(q_, k_, v_, abs_pos, length, window,
                                    scale_)
        m_g = jax.lax.pmax(m, model_axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, model_axis)
        acc_g = jax.lax.psum(acc * corr[..., None], model_axis)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        B_loc = q_.shape[0]                        # local batch inside shmap
        return out.reshape(B_loc, 1, Hq, D)

    return body(q, cache.k, cache.v, cache.length).astype(q.dtype)


def cache_update_ctx_parallel(cache: KVCache, k_new, v_new, mesh, *,
                              model_axis="model", data_axes=("data",)):
    """Ring write when the cache seq dim is sharded: only the owning shard
    writes; everyone else passes its slice through."""
    from functools import partial as _partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    C = cache.k.shape[1]
    s = mesh.shape[model_axis]
    C_loc = C // s
    B = cache.k.shape[0]
    ndata = 1
    for a in data_axes:
        ndata *= mesh.shape[a]
    dp = data_axes if B % ndata == 0 else ()

    @_partial(
        shard_map, mesh=mesh,
        in_specs=(P(dp, model_axis, None, None),
                  P(dp, model_axis, None, None),
                  P(dp, None, None, None),
                  P(dp, None, None, None),
                  P()),
        out_specs=(P(dp, model_axis, None, None),
                   P(dp, model_axis, None, None)),
        check_vma=False,
    )
    def body(k_, v_, kn, vn, length):
        j = jax.lax.axis_index(model_axis)
        pos = jnp.mod(length, C)
        owns = (pos >= j * C_loc) & (pos < (j + 1) * C_loc)
        local = jnp.clip(pos - j * C_loc, 0, C_loc - 1)
        k_w = jax.lax.dynamic_update_slice_in_dim(
            k_, kn.astype(k_.dtype), local, axis=1)
        v_w = jax.lax.dynamic_update_slice_in_dim(
            v_, vn.astype(v_.dtype), local, axis=1)
        return (jnp.where(owns, k_w, k_), jnp.where(owns, v_w, v_))

    k, v = body(cache.k, cache.v, k_new, v_new, cache.length)
    return KVCache(k=k, v=v, length=cache.length + 1)
