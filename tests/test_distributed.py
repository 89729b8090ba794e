"""Distributed correctness: d-VMP shard invariance, sharded train/decode.

These spawn subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest process stays single-device.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_dvmp_matches_single_device_vmp():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.dag import PlateSpec
        from repro.core import vmp, dvmp
        key = jax.random.PRNGKey(0)
        k1,k2,k3 = jax.random.split(key,3)
        N = 800
        z = jax.random.bernoulli(k1, 0.4, (N,)).astype(int)
        mus = jnp.array([[ 3., -2.],[-3., 2.]])
        x = mus[z] + 0.7*jax.random.normal(k2,(N,2))
        xd = jnp.zeros((N,0), jnp.int32)
        spec = PlateSpec(n_features=2, latent_card=2)
        cp = vmp.compile_plate(spec)
        prior = vmp.default_prior(cp); init = vmp.symmetry_broken(prior, k3)
        st = vmp.vmp_fit(cp, prior, init, x, xd, 50, 1e-6)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        st2 = dvmp.dvmp_fit(cp, prior, init, x, xd, mesh, ("data",), 50, 1e-6)
        assert np.allclose(st.post.reg.m, st2.post.reg.m, atol=1e-3), "means differ"
        assert abs(float(st.elbo - st2.elbo)) < 1.0, (st.elbo, st2.elbo)
        print("DVMP_OK")
    """)
    assert "DVMP_OK" in out


def test_sharded_train_step_matches_single_device():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from repro.configs import get_config
        from repro.nn import transformer as T
        from repro.train import step as ts
        from repro.train import optimizer as opt
        cfg = get_config("granite-3-2b").reduced()
        key = jax.random.PRNGKey(0)
        params = T.init_model(key, cfg)
        toks = jax.random.randint(key, (8, 64), 0, cfg.vocab)
        batch = ts.TrainBatch(tokens=toks, labels=jnp.roll(toks, -1, 1))
        lr_fn = opt.cosine_schedule(1e-3, 10, 100)
        s0 = ts.init_train_state(params)
        _, m0 = jax.jit(partial(ts.train_step, cfg=cfg, lr_fn=lr_fn))(s0, batch)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        sh = T.Shardings(mesh=mesh, data_axes=("data",), model_axis="model")
        s1 = ts.init_train_state(params)
        _, m1 = jax.jit(partial(ts.train_step, cfg=cfg, sh=sh, lr_fn=lr_fn))(s1, batch)
        a, b = float(m0["loss"]), float(m1["loss"])
        assert abs(a - b) < 5e-2, (a, b)
        print("TRAIN_SHARD_OK", a, b)
    """)
    assert "TRAIN_SHARD_OK" in out


def test_ctx_parallel_decode_matches_single_device():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.nn import transformer as T
        cfg = get_config("glm4-9b").reduced()
        key = jax.random.PRNGKey(0)
        params = T.init_model(key, cfg)
        B, cap = 8, 64
        st0 = T.init_decode_state(params, cfg, B, cap)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        sh = T.Shardings(mesh=mesh, data_axes=("data",), model_axis="model",
                         shard_heads=False)
        st1 = T.init_decode_state(params, cfg, B, cap)
        tok = jnp.zeros((B,1), jnp.int32)
        t0, t1 = tok, tok
        for i in range(6):
            l0, st0 = T.decode_step(params, st0, t0, cfg)
            l1, st1 = T.decode_step(params, st1, t1, cfg, sh)
            t0 = l0.argmax(-1).astype(jnp.int32)
            t1 = l1.argmax(-1).astype(jnp.int32)
            assert (t0 == t1).all(), (i, t0, t1)
            np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                       atol=0.2, rtol=0.05)
        print("DECODE_SHARD_OK")
    """)
    assert "DECODE_SHARD_OK" in out


def test_moe_ep_matches_dense_local():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import MoEConfig
        from repro.nn import moe as M
        cfg = MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0)  # no drops
        key = jax.random.PRNGKey(0)
        d, ff = 32, 64
        x = jax.random.normal(key, (2, 16, d))
        # local (1 shard)
        p1 = M.init_moe(key, d, ff, cfg, ep_shards=1)
        y1, aux1 = M.apply_moe(p1, x, cfg, mesh=None)
        # EP over 4 model shards (same canonical weights, re-laid-out)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        p4 = M.init_moe(key, d, ff, cfg, ep_shards=4)
        y4, aux4 = M.apply_moe(p4, x, cfg, mesh=mesh)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y4),
                                   atol=2e-2, rtol=2e-2)
        # expert_load is LINEAR in tokens -> exact under the data-shard pmean;
        # load_balance is a product of means (slightly estimator-dependent)
        np.testing.assert_allclose(np.asarray(aux1.expert_load),
                                   np.asarray(aux4.expert_load), atol=1e-5)
        assert abs(float(aux1.load_balance) - float(aux4.load_balance)) < 0.3
        print("MOE_EP_OK")
    """)
    assert "MOE_EP_OK" in out
