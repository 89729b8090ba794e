"""End-to-end behaviour of the whole system (paper-level claims)."""

import os
import subprocess
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bayes.drift import LossDriftMonitor
from repro.configs import get_config
from repro.data.tokens import TokenStream, drift_corpus, markov_sequence_fast
from repro.nn import transformer as T
from repro.train import optimizer as opt
from repro.train import step as ts


def test_e2e_training_reduces_loss_below_unigram():
    """Train a small LM for ~60 steps; loss must fall well below log(V)."""
    cfg = get_config("granite-3-2b").reduced()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    state = ts.init_train_state(params)
    toks = markov_sequence_fast(30_000, cfg.vocab, seed=3)
    stream = TokenStream(toks, batch=8, seq=64)
    lr_fn = opt.cosine_schedule(1.5e-3, 10, 200)
    jstep = jax.jit(partial(ts.train_step, cfg=cfg, lr_fn=lr_fn))
    losses = []
    for b in stream.batches(60):
        state, m = jstep(state, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5
    assert losses[-1] < np.log(cfg.vocab) - 0.3


def test_vb_optimizer_learns_and_tracks_uncertainty():
    """The paper's technique as NN trainer: loss falls AND the posterior
    concentrates (per-weight precision grows) as data accumulates."""
    from repro.bayes import vb_optimizer as vb

    cfg = get_config("granite-3-2b").reduced()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    state = ts.init_vb_state(params)
    toks = markov_sequence_fast(30_000, cfg.vocab, seed=4)
    stream = TokenStream(toks, batch=8, seq=64)
    jstep = jax.jit(partial(ts.vb_train_step, cfg=cfg, n_total=3e4, lr=0.05))
    losses = []
    for b in stream.batches(50):
        state, m = jstep(state, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5
    prec = vb.posterior_prec(state.vb, 3e4)
    mean_prec = float(sum(jnp.sum(p) for p in jax.tree_util.tree_leaves(prec))
                      / sum(p.size for p in jax.tree_util.tree_leaves(prec)))
    assert mean_prec > 1.0   # concentrated beyond the unit prior


def test_drift_monitor_fires_on_distribution_shift():
    cfg = get_config("granite-3-2b").reduced()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    state = ts.init_train_state(params)
    corpus = drift_corpus(20_000, cfg.vocab, seed=5)
    lr_fn = opt.cosine_schedule(1.5e-3, 5, 400)
    jstep = jax.jit(partial(ts.train_step, cfg=cfg, lr_fn=lr_fn))
    monitor = LossDriftMonitor.create(threshold=2.0)
    fired_at = None
    n_steps = 60
    for i in range(n_steps):
        # phase 1 for the first 40 steps, phase 2 afterwards
        half = 0 if i < 40 else 20_000
        stream = TokenStream(corpus[half:half + 20_000], batch=8, seq=64,
                             seed=i)
        b = next(iter(stream.batches(1)))
        state, m = jstep(state, b)
        monitor, drifted = monitor.observe(m["loss"])
        if bool(drifted) and fired_at is None:
            fired_at = i
    assert fired_at is not None and fired_at >= 40, fired_at


def test_streaming_pgm_and_nn_share_drift_machinery():
    """Both stacks use the same Page-Hinkley statistics (one engine)."""
    from repro.core.streaming import drift_init, drift_update

    st = drift_init()
    # stable scores -> no drift
    for _ in range(20):
        st, ph = drift_update(st, jnp.asarray(-1.0))
    assert float(ph) < 1.0
    # collapse in score -> drift statistic rises
    for _ in range(10):
        st, ph = drift_update(st, jnp.asarray(-8.0))
    assert float(ph) > 3.0


def test_importing_the_package_leaves_the_backend_alone():
    """No module initializes a JAX backend when imported: on a TPU host that
    would take hold of the chip in every process that imports it (a child
    process that needs the chip then fails or hangs)."""
    code = """
import importlib, pkgutil
import repro
from jax._src import xla_bridge
names = [m.name for m in pkgutil.walk_packages(repro.__path__, "repro.")]
for name in names:
    importlib.import_module(name)
    assert not xla_bridge._backends, name
print("IMPORTED", len(names))
"""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "IMPORTED" in out.stdout
