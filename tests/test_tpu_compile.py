"""The PGM Pallas kernels compile for a described TPU v5e at real widths.

Interpret mode (the rest of the suite) cannot see the TPU's tiling rule or
its fast-memory limit; the chip's compiler, run here against a described
``v5e:2x2`` topology with no chip attached, can.  Shapes are the paper
workloads of ``configs/amidst_pgm.py`` at 2^20 instances (``gmm_large``,
``nb_mixed``, ``fa_plate``), the structure-search family table, and
junction-tree factor tables at serving batch sizes.  A compile that passes
is not a chip run: nothing executes and no result or time is checked.

The topology is described inside a module fixture, never at import: the
TPU library may be loaded by one process at a time, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.amidst_pgm import PGM_WORKLOADS
from repro.kernels import clg_stats, factor_ops, family_counts

N = 1 << 20                   # instances per kernel call
QUERIES = 4096                # evidence rows of one junction-tree call


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text


def _plate(name):
    spec = PGM_WORKLOADS[name].spec
    cont = spec.n_features - len(spec.discrete_features)
    return cont, max(spec.latent_card, 1), spec


@pytest.mark.parametrize("workload", ["gmm_large", "nb_mixed"])
def test_clg_suffstats(one_chip, workload):
    F, K, _ = _plate(workload)
    _compile(lambda d, y, r: clg_stats.clg_suffstats(d, y, r,
                                                     interpret=False),
             one_chip, ((N, F, 1), jnp.float32), ((N, F), jnp.float32),
             ((N, K), jnp.float32))


def test_clg_suffstats_latent(one_chip):
    F, K, spec = _plate("fa_plate")
    L = spec.latent_dim
    _compile(lambda o, h, y, r, s: clg_stats.clg_suffstats_latent(
        o, h, y, r, s, interpret=False), one_chip,
        ((N, F, 1), jnp.float32), ((N, K, L), jnp.float32),
        ((N, F), jnp.float32), ((N, K), jnp.float32),
        ((K, L, L), jnp.float32))


def test_clg_disc_counts(one_chip):
    _, K, spec = _plate("nb_mixed")
    Fd = len(spec.discrete_features)
    C = max(c for _, c in spec.discrete_features)
    _compile(lambda x, r: clg_stats.clg_disc_counts(x, r, C,
                                                    interpret=False),
             one_chip, ((N, Fd), jnp.int32), ((N, K), jnp.float32))


def test_family_counts(one_chip):
    # structure search over 8 discrete variables: 232 candidate families
    # of in-degree <= 2, configurations of up to 4 x 4 x 4
    Fd, M, C = 8, 232, 64
    _compile(lambda x, s, w: family_counts.family_counts(x, s, w, C,
                                                         interpret=False),
             one_chip, ((N, Fd), jnp.int32), ((M, Fd), jnp.int32),
             ((N,), jnp.float32))


# clique tables of a card-3 network (27 x 9) at a serving batch, and one
# factor 2^20 wide (streamed through VMEM in tiles)
FACTORS = [(QUERIES, 27, 9), (4, 8, N)]


@pytest.mark.parametrize("shape", FACTORS)
def test_log_product(one_chip, shape):
    B, M, W = shape
    _compile(lambda a, b: factor_ops.log_product(a, b, interpret=False),
             one_chip, ((B, M, W), jnp.float32), ((B, W), jnp.float32))


@pytest.mark.parametrize("shape", FACTORS)
def test_log_marginalize(one_chip, shape):
    _compile(lambda x: factor_ops.log_marginalize(x, interpret=False),
             one_chip, (shape, jnp.float32))


@pytest.mark.parametrize("shape", FACTORS)
def test_evidence_select(one_chip, shape):
    _compile(lambda x, i: factor_ops.evidence_select(x, i, interpret=False),
             one_chip, (shape, jnp.float32), ((shape[0],), jnp.int32))


def test_cg_weak_marg(one_chip):
    # strong-JT distribute pass: 64 discrete configurations x 3 mixture
    # components of a 4-dimensional continuous block, per query row
    B, M, W, n = 1024, 64, 3, 4
    _compile(lambda lw, mu, sg: factor_ops.cg_weak_marg(lw, mu, sg,
                                                        interpret=False),
             one_chip, ((B, M, W), jnp.float32), ((B, M, W, n), jnp.float32),
             ((B, M, W, n, n), jnp.float32))


def test_disc_lookup_fuses(one_chip, monkeypatch):
    # the discrete-leaf term of the local step at the drift cell's batch:
    # a compare-select chain under its scope, with no gather left in it
    from repro.core import vmp

    monkeypatch.setenv("REPRO_PALLAS_COMPILE", "1")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    _, _, spec = _plate("nb_mixed")
    cp = vmp.compile_plate(spec)
    lay, n = cp.layout, 1 << 18
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        vmp.default_prior(cp))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in (((n, lay.F), jnp.float32), ((n, lay.Fd), jnp.int32),
                          ((n,), jnp.float32))]
    text = jax.jit(lambda p, xc, xd, m: vmp.local_step(
        cp, p, xc, xd, m, backend="pallas")).lower(
            params, *args).compile().as_text()
    lines = text.splitlines()
    gathers = [ln for ln in lines if " gather(" in ln
               and ("vmp.disc_lookup" in ln or "take_along_axis" in ln)]
    assert not gathers, gathers[0]
    assert any("vmp.disc_lookup" in ln for ln in lines)
