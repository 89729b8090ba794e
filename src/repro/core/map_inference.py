"""Scalable MAP / abductive inference — paper §2.2 / ref [18].

The paper's scheme is map-reduce: scatter many candidate assignments
(Monte-Carlo starts), hill-climb each locally, reduce with max.  TPU-native
version: candidates are a batch dimension (vmap), the hill-climb is a
``lax.while_loop`` of coordinate-ascent passes, and the reduce is a
``psum``-free ``lax.pmax``-style argmax — distributed over the mesh with
shard_map when provided.

Supported query: most probable joint configuration of the DISCRETE variables
of a CLG ``BayesianNetwork`` given (possibly continuous) evidence; continuous
non-evidence variables are marginalized approximately by clamping to their
conditional mean given the current discrete configuration (iterated).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core.dag import BayesianNetwork, Variable


def _complete_continuous(
    bn: BayesianNetwork, asg: Dict[str, jnp.ndarray], evidence: Dict[str, jnp.ndarray]
) -> Dict[str, jnp.ndarray]:
    """Set non-evidence continuous vars to their conditional mean (ancestral)."""
    out = dict(asg)
    for v in bn.order:
        if v.is_discrete or v.name in evidence:
            continue
        parents = bn.dag.get_parents(v)
        dpa = [p for p in parents if p.is_discrete]
        cpa = [p for p in parents if not p.is_discrete]
        didx = tuple(out[p.name].astype(jnp.int32) for p in dpa)
        cpd = bn.cpds[v.name]
        mean = cpd.alpha[didx] if dpa else jnp.broadcast_to(
            cpd.alpha, out[bn.order[0].name].shape)
        if cpa:
            beta = cpd.beta[didx] if dpa else cpd.beta
            xc = jnp.stack([out[p.name] for p in cpa], -1)
            mean = mean + (beta * xc).sum(-1)
        out[v.name] = mean
    return out


def map_inference(
    bn: BayesianNetwork,
    evidence: Dict[str, float],
    *,
    n_starts: int = 128,
    n_passes: int = 20,
    seed: int = 0,
    mesh: Optional[Mesh] = None,
    data_axes: Tuple[str, ...] = ("data",),
) -> Tuple[Dict[str, int], float]:
    """Returns (MAP assignment of discrete non-evidence vars, its log-prob)."""
    ev = {k: jnp.asarray(v) for k, v in evidence.items()}
    dvars: List[Variable] = [
        v for v in bn.order if v.is_discrete and v.name not in ev
    ]
    if not dvars:
        raise ValueError("no discrete query variables")
    cards = [v.card for v in dvars]

    def score(states: jnp.ndarray) -> jnp.ndarray:
        """states: [n, Q] int -> log p(states, evidence, cont@mean)."""
        n = states.shape[0]
        asg = {k: jnp.broadcast_to(v, (n,)) for k, v in ev.items()}
        for i, v in enumerate(dvars):
            asg[v.name] = states[:, i]
        asg = _complete_continuous(bn, asg, ev)
        return bn.log_prob(asg)

    def hill_climb(key: jax.Array, n_local: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        keys = jax.random.split(key, len(dvars))
        init = jnp.stack(
            [jax.random.randint(keys[i], (n_local,), 0, c)
             for i, c in enumerate(cards)], axis=1)

        def one_pass(carry):
            states, best, it = carry
            for i, c in enumerate(cards):  # static unroll over query vars
                cand = jnp.stack([states.at[:, i].set(val) for val in range(c)])
                s = jax.vmap(score)(cand)          # [c, n]
                pick = s.argmax(0)
                states = states.at[:, i].set(pick)
            new_best = score(states)
            return states, new_best, it + 1

        def cond(carry):
            _, best, it = carry
            return it < n_passes

        states, best, _ = jax.lax.while_loop(
            cond, one_pass, (init, score(init), jnp.asarray(0)))
        return states, best

    if mesh is None:
        states, best = jax.jit(partial(hill_climb, n_local=n_starts))(
            jax.random.PRNGKey(seed))
    else:
        ndev = 1
        for a in data_axes:
            ndev *= mesh.shape[a]
        keys = jax.random.split(jax.random.PRNGKey(seed), ndev)

        @partial(shard_map, mesh=mesh, in_specs=P(data_axes),
                 out_specs=(P(data_axes), P(data_axes)), check_vma=False)
        def block(k):
            return hill_climb(k[0], max(n_starts // ndev, 1))

        states, best = jax.jit(block)(keys)

    idx = int(jnp.argmax(best))
    assignment = {v.name: int(states[idx, i]) for i, v in enumerate(dvars)}
    return assignment, float(best[idx])
