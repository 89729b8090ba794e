"""The one data generator: mixture instances made on the device from a seed.

A configuration fixes the model's shape (continuous leaves, discrete leaves
and their cardinalities, latent classes) and the concepts' parameters
(component means, noise, discrete tables), drawn from its own
``concept_seed``.  A traffic mix fixes how many batches, their size, how
many concepts and how often they switch.  The run's ``--seed`` draws only
the instances (class, noise, discrete values), so every seed does the same
kind of work on different rows.

Semantics follow ``gmm_stream`` and ``nb_stream`` of the synthetic data
module: a uniform latent class, Gaussian leaves around the class mean with
a shared noise scale, and discrete leaves drawn from per-class tables.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int) -> jax.Array:
    """A key from a whole number of any size (``jax.random.key`` keeps only
    its low 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def concepts(cfg: dict, n_concepts: int):
    """Per-concept means ``[C, K, F]`` and discrete tables ``[C, K, Fd, M]``
    (``M`` the largest cardinality; categories past a leaf's own are 0)."""
    g = cfg["generator"]
    K, F, cards = cfg["latent_card"], cfg["continuous"], cfg["discrete_cards"]
    M = max(cards, default=1)
    key = key_of(g["concept_seed"])
    means, tables = [], []
    for c in range(n_concepts):
        k1, k2 = jax.random.split(jax.random.fold_in(key, c))
        means.append(jax.random.uniform(k1, (K, F), minval=g["mean_low"],
                                        maxval=g["mean_high"]))
        t = jax.random.dirichlet(
            k2, jnp.full((M,), g.get("dirichlet", 1.0)), (K, len(cards)))
        live = (jnp.arange(M)[None, :] < jnp.asarray(cards or [M])[:, None])
        t = t * live[None]
        tables.append(t / t.sum(-1, keepdims=True))
    return jnp.stack(means), jnp.stack(tables)


@partial(jax.jit, static_argnames=("n_batches", "batch", "K", "Fd",
                                   "n_concepts", "switch_every"))
def _draw(key, means, tables, noise, *, n_batches, batch, K, Fd, n_concepts,
          switch_every):
    t = jnp.arange(n_batches)
    concept = ((t // switch_every) % n_concepts if switch_every
               else jnp.zeros_like(t))
    kz, kx, kd = jax.random.split(key, 3)
    z = jax.random.randint(kz, (n_batches, batch), 0, K)
    mu = means[concept[:, None], z]                          # [T, B, F]
    xc = mu + noise * jax.random.normal(kx, mu.shape)
    if Fd == 0:
        return xc, jnp.zeros((n_batches, batch, 0), jnp.int32)
    logits = jnp.log(tables[concept[:, None], z])            # [T, B, Fd, M]
    xd = jax.random.categorical(kd, logits, axis=-1).astype(jnp.int32)
    return xc, xd


def batches(cfg: dict, traffic: dict, seed: int, n_batches: int, batch: int,
            device=None):
    """``n_batches`` batches of ``batch`` instances, made on ``device`` in
    one jitted call: ``(xc [T, B, F] float32, xd [T, B, Fd] int32)``."""
    n_concepts = traffic.get("concepts", 1)
    means, tables = concepts(cfg, n_concepts)
    args = (key_of(seed), means, tables,
            jnp.float32(cfg["generator"]["noise"]))
    if device is not None:
        args = jax.device_put(args, device)
    return _draw(*args, n_batches=n_batches, batch=batch,
                 K=cfg["latent_card"], Fd=len(cfg["discrete_cards"]),
                 n_concepts=n_concepts,
                 switch_every=traffic.get("switch_every", 0))


def host_batches(cfg, traffic, seed, n_batches, batch):
    """As :func:`batches`, brought to the host once."""
    xc, xd = batches(cfg, traffic, seed, n_batches, batch)
    out = np.asarray(xc), np.asarray(xd)
    del xc, xd
    return out
