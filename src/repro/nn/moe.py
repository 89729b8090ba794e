"""Mixture-of-Experts layer with explicit expert-parallel sharding.

Routing: top-k softmax gating with capacity-based dispatch (GShard-style,
drop on overflow) — index/scatter based, NEVER materializing a [T, E, C]
one-hot.  The d-VMP connection (DESIGN.md §4): router load-balance
statistics are *expected sufficient statistics* summed over the data axis —
the aux loss reduces them with the same psum pattern as the paper's global
parameter messages.

Expert parallelism (the shard_map island): activations between blocks are
sharded over the data axes and REPLICATED over 'model'; therefore each model
shard can locally gather the tokens routed to ITS experts — dispatch needs
no all-to-all at all, and the only collective is one psum over 'model' to
combine partial expert outputs (identical collective shape to the dense
tensor-parallel MLP).  This is the TPU-native reformulation of GPU EP
all-to-all, exploiting activation replication that megatron-style TP
already pays for.

Weight layout: EP-layout tensors [s, E_loc, d, ff_loc] where s = model-axis
size, created by ``ep_split`` at init:
  * E >= s  : E_loc = E // s, ff_loc = ff   (whole experts per shard)
  * E <  s  : E_loc = 1, ff_loc = ff*E // s (experts tensor-split over ff)
Storage sharding: P('model', None, 'data'|None, None) — the 'data' factor is
the FSDP axis for training.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.configs.base import MoEConfig
from repro.nn.layers import he_init

Params = Dict[str, jnp.ndarray]


def ep_split(w: jnp.ndarray, s: int) -> jnp.ndarray:
    """[E, d, ff] canonical -> EP layout [s, E_loc, d, ff_loc]."""
    E, d, ff = w.shape
    if E >= s:
        assert E % s == 0, (E, s)
        return w.reshape(s, E // s, d, ff)
    assert s % E == 0, (E, s)
    k = s // E
    w = w.reshape(E, d, k, ff // k)
    return jnp.transpose(w, (0, 2, 1, 3)).reshape(s, 1, d, ff // k)


def ep_split_down(w: jnp.ndarray, s: int) -> jnp.ndarray:
    """[E, ff, d] -> [s, E_loc, ff_loc, d]."""
    E, ff, d = w.shape
    if E >= s:
        return w.reshape(s, E // s, ff, d)
    k = s // E
    w = w.reshape(E, k, ff // k, d)
    return w.reshape(s, 1, ff // k, d)


def init_moe(key, d: int, ff: int, cfg: MoEConfig, ep_shards: int = 1,
             dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    E = cfg.n_experts
    return {
        "router": he_init(ks[0], (d, E), d, jnp.float32),  # router in fp32
        "w_gate": ep_split(he_init(ks[1], (E, d, ff), d, dtype), ep_shards),
        "w_up": ep_split(he_init(ks[2], (E, d, ff), d, dtype), ep_shards),
        "w_down": ep_split_down(
            he_init(ks[3], (E, ff, d), ff, dtype), ep_shards),
    }


class MoEAux(NamedTuple):
    load_balance: jnp.ndarray   # scalar aux loss (Switch-style)
    router_z: jnp.ndarray       # router z-loss
    expert_load: jnp.ndarray    # [E] fraction of tokens per expert


def _route(router_w: jnp.ndarray, x: jnp.ndarray, cfg: MoEConfig
           ) -> Tuple[jnp.ndarray, jnp.ndarray, MoEAux]:
    """x: [T, d] -> (gates [T, K], expert idx [T, K], aux)."""
    logits = x.astype(jnp.float32) @ router_w                # [T, E]
    probs = jax.nn.softmax(logits, -1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)              # [T, K]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # Switch aux: E * sum_e (frac tokens to e) * (mean prob of e)
    T = x.shape[0]
    onehot_top1 = jax.nn.one_hot(idx[:, 0], cfg.n_experts)
    frac = onehot_top1.mean(0)
    lb = cfg.n_experts * (frac * probs.mean(0)).sum()
    zl = (jax.nn.logsumexp(logits, -1) ** 2).mean()
    return gate, idx, MoEAux(load_balance=lb, router_z=zl, expert_load=frac)


def _dispatch_compute(params: Params, x2: jnp.ndarray, cfg: MoEConfig,
                      shard_idx: jnp.ndarray, s: int) -> Tuple[jnp.ndarray, MoEAux]:
    """Local (per-shard) MoE computation on x2: [T, d].

    ``shard_idx``: this shard's index along the model axis (0 when s == 1).
    Returns the PARTIAL output (needs psum over 'model' when s > 1).
    """
    T, d = x2.shape
    E, K = cfg.n_experts, cfg.top_k
    wg, wu, wd = params["w_gate"][0], params["w_up"][0], params["w_down"][0]
    E_loc, _, ff_loc = wg.shape

    gate, idx, aux = _route(params["router"], x2, cfg)

    flat_e = idx.reshape(-1)                                  # [T*K]
    flat_g = gate.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)

    # position of each (token, k) within its expert's capacity buffer
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)           # [T*K, E]
    pos = (jnp.cumsum(oh, 0) - 1)[jnp.arange(T * K), flat_e]  # [T*K]
    cap = int(math.ceil(T * K * cfg.capacity_factor / E))
    cap = max(8, ((cap + 7) // 8) * 8)
    keep = (pos < cap)

    # map global expert id -> local slot on this shard (or drop)
    if E >= s:
        e0 = shard_idx * E_loc
        mine = (flat_e >= e0) & (flat_e < e0 + E_loc) & keep
        local_e = jnp.clip(flat_e - e0, 0, E_loc - 1)
    else:  # each expert split over s//E shards; every owning shard takes it
        owner = flat_e * (s // E)                              # first owner
        span = s // E
        mine = (shard_idx >= owner) & (shard_idx < owner + span) & keep
        local_e = jnp.zeros_like(flat_e)

    posc = jnp.clip(pos, 0, cap - 1)
    w = mine.astype(jnp.bfloat16)
    buf = jnp.zeros((E_loc, cap, d), jnp.bfloat16)
    buf = buf.at[local_e, posc].add(
        x2.astype(jnp.bfloat16)[flat_t] * w[:, None])

    h_g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg.astype(jnp.bfloat16)))
    h_u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(jnp.bfloat16))
    out_buf = jnp.einsum("ecf,efd->ecd", h_g * h_u, wd.astype(jnp.bfloat16))

    y = jnp.zeros((T, d), jnp.float32)
    contrib = out_buf[local_e, posc] * (flat_g * mine).astype(jnp.float32)[:, None]
    y = y.at[flat_t].add(contrib.astype(jnp.float32))
    return y, aux


def apply_moe(params: Params, x: jnp.ndarray, cfg: MoEConfig,
              mesh: Optional[Mesh] = None, model_axis: str = "model",
              data_axes: Tuple[str, ...] = ("data",)) -> Tuple[jnp.ndarray, MoEAux]:
    """x: [B, S, d] -> (y [B, S, d], aux). shard_map EP when mesh given."""
    B, S, d = x.shape

    if mesh is None:
        y, aux = _dispatch_compute(params, x.reshape(B * S, d), cfg,
                                   jnp.asarray(0), 1)
        return y.reshape(B, S, d).astype(x.dtype), aux

    s = mesh.shape[model_axis]
    ndata = 1
    for a in data_axes:
        ndata *= mesh.shape[a]
    if B % ndata != 0:
        data_axes = ()   # tiny decode batches stay replicated over data

    @partial(
        shard_map, mesh=mesh,
        in_specs=(
            {"router": P(), "w_gate": P(model_axis), "w_up": P(model_axis),
             "w_down": P(model_axis)},
            P(data_axes, None, None),
        ),
        out_specs=(P(data_axes, None, None), P()),
        check_vma=False,
    )
    def body(pr, xl):
        Bl, Sl, _ = xl.shape
        sidx = jax.lax.axis_index(model_axis)
        y, aux = _dispatch_compute(pr, xl.reshape(Bl * Sl, d), cfg, sidx, s)
        # bf16 psum (§Perf change A): halves the EP combine link bytes
        y = jax.lax.psum(y.astype(jnp.bfloat16), model_axis)
        aux = jax.tree_util.tree_map(
            lambda a: jax.lax.pmean(a, data_axes + (model_axis,)), aux)
        return y.reshape(Bl, Sl, d), aux

    y, aux = body(params, x)
    return y.astype(x.dtype), aux
