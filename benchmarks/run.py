"""Benchmark harness — one function per paper claim (DESIGN.md §7.5).

Prints ``name,us_per_call,derived`` CSV rows.  The paper is a toolbox paper
without numeric tables; the benchmarks instantiate its CLAIMS:

  (i)    parallel VMP scales with batched instances (multi-core -> vmap)
  (iii)  streaming VB is constant-memory and tracks the batch posterior
  (iv)   drift detection flags synthetic concept drift
  (v)    model zoo recovers ground truth (Table 2)
  (vi)   parallel importance sampling throughput + ESS
  (vii)  kernels (interpret mode — correctness-grade timing only)
  (viii) end-to-end LM training throughput (reduced configs)
  (ix)   exact (junction tree) vs approximate (IS, VMP) posterior accuracy
         and throughput — the paper's HUGIN link, replaced natively

(d-VMP shard invariance — claim (ii) — is exercised in
tests/test_distributed.py and at 256/512-chip scale by the dry-run.)
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial

import numpy as np

# Emitted by --json mode; every PR appends a measured before/after point so
# the perf trajectory of ROADMAP's "as fast as the hardware allows" is a
# recorded artifact, not a claim.
BENCH_STREAMING_SCHEMA = {
    "bench": str, "schema_version": int, "created": str, "backend": str,
    "config": dict, "results": list, "speedup_inst_per_s": float,
}

# --json --dvmp mode: the distributed mesh path (shard_map + psum) vs the
# single-device fit on identical data — the d-VMP claim (ii) as a JSON
# artifact (ROADMAP open item "a JSON mode for the d-VMP mesh path").
BENCH_DVMP_SCHEMA = {
    "bench": str, "schema_version": int, "created": str, "backend": str,
    "config": dict, "results": list, "speedup_inst_per_s": float,
    "posterior_max_abs_diff": float,
}

# --json --latent mode: the latent-plate (FA/PPCA) E-step einsum vs the fused
# component-major Pallas kernel, plus strong-junction-tree query throughput
# with and without shape-bucketed clique propagation.
BENCH_LATENT_SCHEMA = {
    "bench": str, "schema_version": int, "created": str,
    "config": dict, "results": list,
    "latent_backend_max_rel_diff": float,
    "jt_posterior_max_abs_diff": float,
    "jt_bucketed_speedup": float,
}

# --json --structure mode: the structure-learning workload — batched family
# scoring throughput (family_counts kernel vs einsum), Chow-Liu edge
# recovery and hill-climbing wall-clock/skeleton-F1 on ground-truth
# synthetic networks.
BENCH_STRUCTURE_SCHEMA = {
    "bench": str, "schema_version": int, "created": str,
    "config": dict, "results": list,
    "family_score_max_abs_diff": float,
    "chowliu_edge_f1": float,
    "hillclimb_skeleton_f1": float,
}


# --json --temporal mode: the fused temporal hot path — the whole-fit
# lax.scan (dynamic HMM family) vs the seed-style host sweep loop at
# B=512/T=64, the chain-parallel fHMM suff-stats backends, fused/unfused
# posterior parity and the compiled-program (no-retrace) flag.
BENCH_TEMPORAL_SCHEMA = {
    "bench": str, "schema_version": int, "created": str,
    "config": dict, "results": list,
    "speedup_seq_per_s": float,
    "fused_posterior_max_abs_diff": float,
    "fhmm_backend_max_abs_diff": float,
    "retrace_free": bool,
}


# --json --serve mode: the async serving tier — sustained queries/s and
# request/bucket latency percentiles vs offered load, single-device vs
# mesh-replica, plan-cache hit rate and the hot-swap zero-drop flag.
BENCH_SERVE_SCHEMA = {
    "bench": str, "schema_version": int, "created": str,
    "config": dict, "results": list,
    "plan_cache_hit_rate": float,
    "hot_swap_zero_drop": bool,
}


# --json --resilience mode: the fault-tolerance layer under injected
# faults — streaming throughput with a 1%-NaN-poisoned stream vs clean
# (plus the quarantine bit-identity flag), serving qps/p99 through a
# worker crash + transient compile failure vs clean (plus the zero-loss
# flag), and checkpoint save/restore/recovery timings with the
# bit-identical-resume flag.
BENCH_RESILIENCE_SCHEMA = {
    "bench": str, "schema_version": int, "created": str,
    "config": dict, "streaming": dict, "serving": dict, "checkpoint": dict,
    "quarantine_bit_identical": bool,
    "serve_zero_loss": bool,
    "resume_bit_identical": bool,
}


def _bench_env_config() -> dict:
    """Environment fields stamped into every BENCH_*.json config block so
    the perf trajectory is comparable across jax versions / kernel policies."""
    import jax

    from repro.kernels import clg_stats

    return {
        "device": str(jax.devices()[0]).split(":")[0],
        "jax_version": jax.__version__,
        "pallas_policy": ("interpret" if clg_stats._resolve_interpret(None)
                          else "compiled"),
    }


def _t(fn, *args, reps=3, warmup=1, **kw):
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def bench_vmp_parallel():
    """(i) E-step throughput vs batch size — the parallelStream analog."""
    import jax
    import jax.numpy as jnp

    from repro.core import vmp
    from repro.core.dag import PlateSpec

    spec = PlateSpec(n_features=10, latent_card=4)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    post = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    step = jax.jit(lambda x, xd, m: vmp.local_step(cp, post, x, xd, m))
    for n in (1_000, 10_000, 100_000):
        x = jax.random.normal(jax.random.PRNGKey(1), (n, 10))
        xd = jnp.zeros((n, 0), jnp.int32)
        us = _t(step, x, xd, jnp.ones(n))
        print(f"vmp_estep_n{n},{us:.0f},{n / us * 1e6:.0f} inst/s")


def bench_streaming():
    """(iii) streaming VB: batches/sec at fixed memory."""
    import jax

    from repro.core import streaming, vmp
    from repro.core.dag import PlateSpec
    from repro.data.synthetic import gmm_stream

    stream, _, _ = gmm_stream(50_000, 3, 8, seed=0)
    spec = PlateSpec(n_features=8, latent_card=3)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    ss = streaming.stream_init(
        prior, vmp.symmetry_broken(prior, jax.random.PRNGKey(0)))
    t0 = time.perf_counter()
    nb = 0
    for b in stream.batches(2_000):
        ss, info = streaming.stream_update(cp, prior, ss, b.xc, b.xd,
                                           sweeps=5)
        nb += 1
    dt = time.perf_counter() - t0
    print(f"streaming_vb_batch2000,{dt / nb * 1e6:.0f},"
          f"{50_000 / dt:.0f} inst/s elbo={float(info['elbo']):.1f}")


def register_estimators() -> None:
    """Register the bench-side obs estimators:

    * ``"hlo_cost"`` — the analytical HLO cost model
      (``hlo_analysis.analyze``, dormant since seed); estimates flow back
      into BENCH_* results via :func:`_program_analysis`.
    * ``"achieved_vs_peak"`` — ``roofline.achieved_vs_peak``: measured
      seconds + analytical FLOPs/bytes -> fraction-of-roof and
      compute/memory bound classification (the live half of the ROADMAP
      roofline gate; peaks from ``roofline.PEAKS`` by device kind).

    When obs is enabled every estimate is also a ``bench_estimate``
    JSONL event."""
    from repro import obs

    if not obs.registered("hlo_cost"):
        try:
            import hlo_analysis                  # script mode (sys.path[0])
        except ImportError:
            from benchmarks import hlo_analysis  # repo-root import

        def hlo_cost(hlo_text: str) -> dict:
            a = hlo_analysis.analyze(hlo_text)
            return {"flops": a.get("flops"),
                    "hbm_bytes": a.get("hbm_bytes"),
                    "hbm_bytes_min": a.get("hbm_bytes_min"),
                    "collective_bytes": a.get("collective_bytes")}

        obs.register("hlo_cost", hlo_cost)

    if not obs.registered("achieved_vs_peak"):
        try:
            import roofline                      # script mode (sys.path[0])
        except ImportError:
            from benchmarks import roofline      # repo-root import
        obs.register("achieved_vs_peak", roofline.achieved_vs_peak)


def _achieved_vs_peak_row(analytical, us_per_call: float):
    """achieved-vs-peak stamp for one bench row: analytical FLOP/byte
    counts + the measured per-call time -> fraction-of-roof dict (None
    when the cost model produced nothing to score)."""
    import jax

    from repro import obs

    if not analytical or not analytical.get("flops"):
        return None
    if not obs.registered("achieved_vs_peak"):
        return None
    if jax.devices()[0].platform != "tpu":
        return None     # a host-CPU time is no device roofline share
    return obs.estimate("achieved_vs_peak", seconds=us_per_call / 1e6,
                        flops=analytical["flops"],
                        hbm_bytes=analytical.get("hbm_bytes_min"))


def _program_analysis(lowered):
    """(peak_mem_bytes, analytical) of a lowered program — ONE compile
    shared by the peak-memory proxy and the registered ``hlo_cost``
    analytical FLOP/byte model.  A program that does not compile raises."""
    from repro import obs

    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    peak = float(ma.temp_size_in_bytes + ma.argument_size_in_bytes
                 + ma.output_size_in_bytes)
    analytical = None
    if obs.registered("hlo_cost"):
        analytical = obs.estimate("hlo_cost", compiled.as_text())
    return peak, analytical


def _peak_mem_proxy(lowered):
    """Compiled-program peak-memory proxy in bytes."""
    return _program_analysis(lowered)[0]


def bench_streaming_json(n: int = 50_000, batch: int = 2_000,
                         sweeps: int = 5, k: int = 3, f: int = 8,
                         backend: str = None, out: str = "BENCH_streaming.json",
                         window: int = 5) -> dict:
    """(iii, JSON mode) seed per-batch ``stream_update`` loop vs the fused,
    resident ``stream_fit`` scan (whole stream on device) vs the windowed
    scan (host-resident stream, ``window`` batches on device at a time) on
    the benchmark GMM stream.

    Writes ``out`` with inst/s, us/batch, a peak-memory proxy and the
    suff-stats backend for all three drivers — the perf-trajectory artifact
    this and every future PR updates.
    """
    import datetime

    import jax
    import jax.numpy as jnp

    from repro.core import streaming, vmp
    from repro.core.dag import PlateSpec
    from repro.data.synthetic import gmm_stream

    backend = backend or vmp.default_backend()
    spec = PlateSpec(n_features=f, latent_card=k)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    stream, _, _ = gmm_stream(n, k, f, seed=0)
    batches = list(stream.batches(batch))
    nb = len(batches)
    window = max(1, min(window, nb))

    def run_loop():
        ss = streaming.stream_init(prior, init)
        for b in batches:
            ss, info = streaming.stream_update(cp, prior, ss, b.xc, b.xd,
                                               sweeps=sweeps, mask=b.mask)
        jax.block_until_ready(ss.post.reg.m)
        return ss

    xcs = jnp.stack([b.xc for b in batches])
    xds = jnp.stack([b.xd for b in batches])
    masks = jnp.stack([b.mask for b in batches])
    # the windowed driver's stream stays host-resident (numpy)
    xcs_h, xds_h, masks_h = (np.asarray(xcs), np.asarray(xds),
                             np.asarray(masks))

    def run_scan():
        ss = streaming.stream_init(prior, init)
        ss, infos = streaming.stream_fit(cp, prior, ss, xcs, xds, masks,
                                         sweeps=sweeps, backend=backend)
        jax.block_until_ready(ss.post.reg.m)
        return ss

    def run_windowed():
        ss = streaming.stream_init(prior, init)
        ss, infos = streaming.stream_fit(cp, prior, ss, xcs_h, xds_h,
                                         masks_h, sweeps=sweeps,
                                         backend=backend, window=window)
        jax.block_until_ready(ss.post.reg.m)
        return ss

    results = []
    finals = {}
    for name, fn in (("stream_update_loop", run_loop),
                     ("stream_fit_scan", run_scan),
                     ("stream_fit_windowed", run_windowed)):
        fn()                          # warm the jit caches
        t0 = time.perf_counter()
        finals[name] = fn()
        dt = time.perf_counter() - t0
        results.append({
            "driver": name,
            "backend": backend if name != "stream_update_loop" else "einsum",
            "n_batches": nb,
            "window": window if name == "stream_fit_windowed" else None,
            "us_per_batch": dt / nb * 1e6,
            "inst_per_s": n / dt,
            "peak_mem_bytes": None,
        })

    # peak-mem proxies + analytical FLOP/byte estimates from the compiled
    # scan programs (one compile each — _program_analysis shares it); the
    # loop driver has no single program — proxy with its per-batch fit
    register_estimators()
    ss0 = streaming.stream_init(prior, init)
    results[1]["peak_mem_bytes"], results[1]["analytical"] = \
        _program_analysis(streaming._stream_fit_scan.lower(
            cp, prior, ss0, xcs, xds, masks, sweeps=sweeps, tol=1e-4,
            drift_threshold=5.0, forget=0.3, backend=backend, chunk=None))
    ss0 = streaming.stream_init(prior, init)
    results[2]["peak_mem_bytes"], results[2]["analytical"] = \
        _program_analysis(streaming._stream_fit_scan.lower(
            cp, prior, ss0, xcs[:window], xds[:window], masks[:window],
            sweeps=sweeps, tol=1e-4, drift_threshold=5.0, forget=0.3,
            backend=backend, chunk=None))
    results[0]["peak_mem_bytes"], results[0]["analytical"] = \
        _program_analysis(
            vmp.vmp_fit.lower(cp, prior, init, batches[0].xc, batches[0].xd,
                              sweeps, 1e-4, batches[0].mask, "einsum", None))

    # same posterior from all drivers (parity is also unit-tested)
    drift = max(float(np.abs(
        np.asarray(finals["stream_update_loop"].post.reg.m)
        - np.asarray(finals[d].post.reg.m)).max())
        for d in ("stream_fit_scan", "stream_fit_windowed"))

    payload = {
        "bench": "streaming",
        "schema_version": 2,
        "created": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "backend": backend,
        "config": {"n": n, "batch": batch, "sweeps": sweeps,
                   "features": f, "components": k, "window": window,
                   **_bench_env_config()},
        "results": results,
        "speedup_inst_per_s": results[1]["inst_per_s"] / results[0]["inst_per_s"],
        "driver_posterior_max_abs_diff": drift,
    }
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    print(f"wrote {out}: stream_fit_scan {payload['speedup_inst_per_s']:.2f}x "
          f"inst/s vs stream_update_loop "
          f"({results[1]['inst_per_s']:.0f} vs {results[0]['inst_per_s']:.0f})")
    return payload


def validate_bench_streaming(payload: dict) -> None:
    """Schema gate used by scripts/ci.sh — raises on any malformed field."""
    for key, typ in BENCH_STREAMING_SCHEMA.items():
        if key not in payload:
            raise ValueError(f"BENCH_streaming.json missing key {key!r}")
        if typ is float and isinstance(payload[key], int):
            continue
        if not isinstance(payload[key], typ):
            raise ValueError(f"{key!r} must be {typ.__name__}, "
                             f"got {type(payload[key]).__name__}")
    drivers = {r["driver"] for r in payload["results"]}
    if drivers != {"stream_update_loop", "stream_fit_scan",
                   "stream_fit_windowed"}:
        raise ValueError(f"unexpected drivers {drivers}")
    for key in ("jax_version", "pallas_policy"):
        if key not in payload["config"]:
            raise ValueError(f"config missing {key!r}")
    for r in payload["results"]:
        for field in ("backend", "n_batches", "window", "us_per_batch",
                      "inst_per_s", "peak_mem_bytes"):
            if field not in r:
                raise ValueError(f"result {r['driver']} missing {field!r}")
        if not r["inst_per_s"] > 0:
            raise ValueError("inst_per_s must be positive")


def bench_dvmp_json(n: int = 50_000, sweeps: int = 5, k: int = 3, f: int = 8,
                    backend: str = None, n_devices: int = 0,
                    out: str = "BENCH_dvmp.json") -> dict:
    """(ii, JSON mode) d-VMP over the device mesh vs single-device VMP.

    Same data, same sweep count; the mesh driver is the `shard_map` body
    with one ``lax.psum`` of the suff-stats pytree per sweep.  Writes
    ``out`` with inst/s, us/fit and the replicated-posterior max-abs-diff
    (shard invariance — must stay at float-reduction-order noise).
    """
    import datetime

    import jax

    from repro.core import dvmp, vmp
    from repro.launch.mesh import make_mesh
    from repro.core.dag import PlateSpec
    from repro.data.synthetic import gmm_stream

    backend = backend or vmp.default_backend()
    ndev = n_devices or len(jax.devices())
    if n < ndev:
        raise ValueError(f"--n {n} must be >= the mesh size {ndev}")
    n = (n // ndev) * ndev                      # shardable leading dim
    spec = PlateSpec(n_features=f, latent_card=k)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    stream, _, _ = gmm_stream(n, k, f, seed=0)
    batch = stream.collect()
    xc, xd = batch.xc, batch.xd
    mesh = make_mesh((ndev,), ("data",))

    def run_single():
        st = vmp.vmp_fit(cp, prior, init, xc, xd, sweeps, 0.0,
                         None, backend, None)
        jax.block_until_ready(st.post.reg.m)
        return st

    def run_mesh():
        st = dvmp.dvmp_fit(cp, prior, init, xc, xd, mesh, ("data",),
                           sweeps, 0.0, backend=backend)
        jax.block_until_ready(st.post.reg.m)
        return st

    results = []
    finals = {}
    for name, fn in (("vmp_single_device", run_single),
                     ("dvmp_mesh", run_mesh)):
        fn()                                    # warm the jit caches
        t0 = time.perf_counter()
        finals[name] = fn()
        dt = time.perf_counter() - t0
        results.append({
            "driver": name,
            "backend": backend,
            "n_devices": 1 if name == "vmp_single_device" else ndev,
            "us_per_fit": dt * 1e6,
            "inst_per_s": n * sweeps / dt,
        })

    diff = float(np.abs(
        np.asarray(finals["vmp_single_device"].post.reg.m)
        - np.asarray(finals["dvmp_mesh"].post.reg.m)).max())
    # analytical FLOP/byte estimate of the compiled mesh-fit program
    register_estimators()
    prog = dvmp._fit_program(cp, mesh, ("data",), sweeps, 0.0, backend, None)
    _, analytical = _program_analysis(
        prog.lower(prior, init, xc, xd,
                   jax.numpy.ones(xc.shape[0], xc.dtype)))
    payload = {
        "bench": "dvmp",
        "schema_version": 1,
        "created": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "backend": backend,
        "config": {"n": n, "sweeps": sweeps, "features": f, "components": k,
                   "mesh_shape": [ndev], "analytical_mesh_fit": analytical,
                   **_bench_env_config()},
        "results": results,
        "speedup_inst_per_s": results[1]["inst_per_s"]
        / results[0]["inst_per_s"],
        "posterior_max_abs_diff": diff,
    }
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    print(f"wrote {out}: dvmp_mesh x{ndev} {payload['speedup_inst_per_s']:.2f}x"
          f" inst/s vs single device (posterior diff {diff:.2e})")
    return payload


def validate_bench_dvmp(payload: dict) -> None:
    """Schema gate for BENCH_dvmp.json — used by scripts/ci.sh."""
    for key, typ in BENCH_DVMP_SCHEMA.items():
        if key not in payload:
            raise ValueError(f"BENCH_dvmp.json missing key {key!r}")
        if typ is float and isinstance(payload[key], int):
            continue
        if not isinstance(payload[key], typ):
            raise ValueError(f"{key!r} must be {typ.__name__}, "
                             f"got {type(payload[key]).__name__}")
    drivers = {r["driver"] for r in payload["results"]}
    if drivers != {"vmp_single_device", "dvmp_mesh"}:
        raise ValueError(f"unexpected drivers {drivers}")
    for key in ("jax_version", "pallas_policy"):
        if key not in payload["config"]:
            raise ValueError(f"config missing {key!r}")
    for r in payload["results"]:
        for field in ("backend", "n_devices", "us_per_fit", "inst_per_s"):
            if field not in r:
                raise ValueError(f"result {r['driver']} missing {field!r}")
        if not r["inst_per_s"] > 0:
            raise ValueError("inst_per_s must be positive")
    if not payload["posterior_max_abs_diff"] < 1e-2:
        raise ValueError(
            "d-VMP shard invariance violated: posterior_max_abs_diff="
            f"{payload['posterior_max_abs_diff']}")


def bench_latent_json(n: int = 8_192, f: int = 4, k: int = 3,
                      latent_dims: tuple = (2, 8), depth: int = 12,
                      b: int = 32, reps: int = 5,
                      out: str = "BENCH_latent.json") -> dict:
    """(i/ix, JSON mode) the latent-plate perf trail.

    Part 1 — FA/PPCA-mixture E-step (``local_step`` with L > 0): the einsum
    reference vs the fused component-major ``clg_suffstats_latent`` Pallas
    kernel, per latent dimension in ``latent_dims``; records inst/s for
    both backends and their max relative suff-stat difference (the fused
    path must match the reference wherever it runs).

    Part 2 — strong-junction-tree queries on a depth-``depth`` CLG chain
    (Z -> X0 -> ... -> X_{depth-1}, batched evidence on the last node):
    per-clique propagation vs shape-bucketed propagation, queries/s both
    ways plus the posterior max-abs-diff (must be ~0).
    """
    import datetime

    import jax
    import jax.numpy as jnp

    from repro.core import expfam as ef
    from repro.core import vmp
    from repro.core.dag import (BayesianNetwork, CLGCPD, DAG, MultinomialCPD,
                                PlateSpec, Variables)
    from repro.infer_exact import JunctionTreeEngine

    register_estimators()
    results = []

    # -- part 1: latent-plate E-step backends --------------------------------
    rel_diff = 0.0
    for L in latent_dims:
        spec = PlateSpec(n_features=f, latent_card=k, latent_dim=L)
        cp = vmp.compile_plate(spec)
        prior = vmp.default_prior(cp)
        post = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
        xc = jax.random.normal(jax.random.PRNGKey(1), (n, f))
        xd = jnp.zeros((n, 0), jnp.int32)
        mask = jnp.ones(n)
        stats = {}
        for backend in ("einsum", "pallas"):
            step = jax.jit(lambda x, d, m, be=backend: vmp.local_step(
                cp, post, x, d, m, backend=be))
            us = _t(step, xc, xd, mask, reps=reps)
            _, analytical = _program_analysis(step.lower(xc, xd, mask))
            row = {
                "driver": f"local_step_L{L}", "backend": backend, "L": L,
                "n": n, "us_per_call": us, "inst_per_s": n / us * 1e6,
            }
            avp = _achieved_vs_peak_row(analytical, us)
            if avp is not None:
                row["achieved_vs_peak"] = avp
            results.append(row)
            stats[backend] = step(xc, xd, mask)[0]
        de = np.asarray(ef.reg_dense(stats["einsum"].reg).sxx)
        dp = np.asarray(ef.reg_dense(stats["pallas"].reg).sxx)
        rel_diff = max(rel_diff,
                       float((np.abs(de - dp) / (1.0 + np.abs(de))).max()))

    # -- part 2: strong JT on a deep chain, bucketed vs per-clique -----------
    vs = Variables()
    Z = vs.new_multinomial("Z", 3)
    xs = [vs.new_gaussian(f"X{i:02d}") for i in range(depth)]
    dag = DAG(vs)
    dag.add_parent(xs[0], Z)
    for a_, b_ in zip(xs, xs[1:]):
        dag.add_parent(b_, a_)
    rng = np.random.RandomState(0)
    cpds = {"Z": MultinomialCPD(jnp.asarray(rng.dirichlet(np.ones(3)))),
            xs[0].name: CLGCPD(jnp.asarray(rng.randn(3)),
                               jnp.zeros((3, 0)), jnp.ones(3))}
    for a_, b_ in zip(xs, xs[1:]):
        cpds[b_.name] = CLGCPD(jnp.asarray(rng.randn()),
                               jnp.asarray(rng.randn(1) * 0.8),
                               jnp.asarray(0.3 + rng.rand()))
    bn = BayesianNetwork(dag, cpds)
    ev = {xs[-1].name: rng.randn(b).astype(np.float32)}
    post_z = {}
    for name, bucketed in (("strong_jt_per_clique", False),
                           ("strong_jt_bucketed", True)):
        eng = JunctionTreeEngine(bn, bucketed=bucketed)
        eng.set_evidence(ev)
        eng.run_inference()                   # compile + warm
        t0 = time.perf_counter()
        for _ in range(reps):
            eng.run_inference()
            pz = eng.posterior_discrete(Z)
        jax.block_until_ready(pz)
        dt = (time.perf_counter() - t0) / reps
        post_z[name] = np.asarray(pz)
        results.append({
            "driver": name, "depth": depth, "batch": b,
            "us_per_batch": dt * 1e6, "queries_per_s": b / dt,
        })
    jt_diff = float(np.abs(post_z["strong_jt_bucketed"]
                           - post_z["strong_jt_per_clique"]).max())
    jt_speedup = (results[-1]["queries_per_s"]
                  / results[-2]["queries_per_s"])

    payload = {
        "bench": "latent",
        "schema_version": 1,
        "created": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "config": {"n": n, "features": f, "components": k,
                   "latent_dims": list(latent_dims), "jt_depth": depth,
                   "jt_batch": b, **_bench_env_config()},
        "results": results,
        "latent_backend_max_rel_diff": rel_diff,
        "jt_posterior_max_abs_diff": jt_diff,
        "jt_bucketed_speedup": jt_speedup,
    }
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    print(f"wrote {out}: latent backends rel diff {rel_diff:.2e}; "
          f"strong JT bucketed {jt_speedup:.2f}x "
          f"({results[-1]['queries_per_s']:.0f} vs "
          f"{results[-2]['queries_per_s']:.0f} q/s, diff {jt_diff:.2e})")
    return payload


def validate_bench_latent(payload: dict) -> None:
    """Schema gate for BENCH_latent.json — used by scripts/ci.sh."""
    for key, typ in BENCH_LATENT_SCHEMA.items():
        if key not in payload:
            raise ValueError(f"BENCH_latent.json missing key {key!r}")
        if typ is float and isinstance(payload[key], int):
            continue
        if not isinstance(payload[key], typ):
            raise ValueError(f"{key!r} must be {typ.__name__}, "
                             f"got {type(payload[key]).__name__}")
    for key in ("jax_version", "pallas_policy"):
        if key not in payload["config"]:
            raise ValueError(f"config missing {key!r}")
    drivers = {r["driver"] for r in payload["results"]}
    for need in ("strong_jt_per_clique", "strong_jt_bucketed"):
        if need not in drivers:
            raise ValueError(f"missing driver {need!r}")
    if not any(d.startswith("local_step_L") for d in drivers):
        raise ValueError("missing local_step latent drivers")
    backends = {r.get("backend") for r in payload["results"]
                if r["driver"].startswith("local_step_L")}
    if backends != {"einsum", "pallas"}:
        raise ValueError(f"latent drivers must cover both backends, "
                         f"got {backends}")
    if not payload["latent_backend_max_rel_diff"] < 1e-4:
        raise ValueError(
            "fused latent path diverged from the einsum reference: "
            f"rel diff {payload['latent_backend_max_rel_diff']}")
    if not payload["jt_posterior_max_abs_diff"] < 1e-5:
        raise ValueError(
            "bucketed strong JT diverged from per-clique propagation: "
            f"{payload['jt_posterior_max_abs_diff']}")


def bench_structure_json(n: int = 20_000, n_vars: int = 8,
                         max_parents: int = 2, card: int = 3, reps: int = 3,
                         out: str = "BENCH_structure.json") -> dict:
    """(JSON mode) the structure-learning perf trail (learn_structure).

    Part 1 — batched family scoring: EVERY candidate family of parent-set
    size <= ``max_parents`` over ``n_vars`` discrete columns, scored in one
    device call per backend (``family_counts`` Pallas kernel vs the einsum
    reference); records families/s both ways plus their max score diff
    (the kernel must match the reference wherever it runs).

    Part 2 — Chow-Liu on a ground-truth random tree: wall-clock + exact
    edge-recovery F1.

    Part 3 — hill-climbing on a bounded-fan-in random discrete BN:
    wall-clock, iterations, cache-miss families scored, skeleton F1.
    """
    import datetime
    import itertools

    from repro.data import synthetic as syn
    from repro.learn_structure import chow_liu, hill_climb, skeleton_f1
    from repro.learn_structure import scores as S

    results = []

    # -- part 1: family-score throughput, einsum vs pallas -------------------
    bn = syn.random_discrete_bn(n_vars, card=card,
                                max_parents=max_parents, seed=0)
    stream = syn.bn_stream(bn, n, seed=1)
    batch = stream.collect()
    cards = [card] * n_vars
    fams = []
    for ch in range(n_vars):
        rest = [v for v in range(n_vars) if v != ch]
        for k in range(max_parents + 1):
            fams.extend((ch, pa) for pa in
                        itertools.combinations(rest, k))
    register_estimators()
    # disc_family_scores mixes host numpy with device calls, so there is
    # no single lowered program to analyze; the closed-form count-kernel
    # model below covers the dominant contraction: one-hot accumulation
    # into each family's joint contingency table (2*n*J FMA per family
    # with J joint states) over an n x n_vars int32 read.
    joint_states = [int(np.prod([cards[ch]] + [cards[p] for p in pa]))
                    for ch, pa in fams]
    fam_flops = float(2 * n * sum(joint_states))
    fam_bytes = float(4 * n * n_vars + 4 * sum(joint_states))
    scores = {}
    for backend in ("einsum", "pallas"):
        def score(be=backend):
            scores[be] = S.disc_family_scores(
                batch.xd, fams, cards, mask=batch.mask, backend=be)
            return scores[be]

        t = _t(score, reps=reps)
        row = {
            "driver": "family_scores", "backend": backend,
            "n": n, "n_families": len(fams), "us_per_call": t,
            "families_per_s": len(fams) / t * 1e6,
        }
        avp = _achieved_vs_peak_row(
            {"flops": fam_flops, "hbm_bytes_min": fam_bytes}, t)
        if avp is not None:
            row["achieved_vs_peak"] = avp
        results.append(row)
    score_diff = float(np.abs(scores["einsum"] - scores["pallas"]).max())

    # -- part 2: Chow-Liu tree recovery --------------------------------------
    tree = syn.random_discrete_bn(n_vars, card=card, seed=3, tree=True)
    ts = syn.bn_stream(tree, n, seed=4)
    tb = ts.collect()
    chow_liu(tb, ts.attributes)                   # warm the jit caches
    t0 = time.perf_counter()
    for _ in range(reps):
        edges, _ = chow_liu(tb, ts.attributes)
    dt = (time.perf_counter() - t0) / reps
    cl_f1 = skeleton_f1(tree, edges)
    results.append({
        "driver": "chowliu", "backend": "einsum", "n": n,
        "n_vars": n_vars, "wallclock_s": dt, "edge_f1": cl_f1,
    })

    # -- part 3: hill-climbing recovery --------------------------------------
    hs = syn.bn_stream(bn, n, seed=5)
    hb = hs.collect()
    hill_climb(hb, hs.attributes, max_parents=max_parents)     # warm
    t0 = time.perf_counter()
    res = hill_climb(hb, hs.attributes, max_parents=max_parents)
    dt = time.perf_counter() - t0
    hc_f1 = skeleton_f1(bn, res.parents)
    results.append({
        "driver": "hillclimb", "backend": "einsum", "n": n,
        "n_vars": n_vars, "max_parents": max_parents, "wallclock_s": dt,
        "n_iters": res.n_iters, "n_families_scored": res.n_scored,
        "skeleton_f1": hc_f1,
    })

    payload = {
        "bench": "structure",
        "schema_version": 1,
        "created": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "config": {"n": n, "n_vars": n_vars, "max_parents": max_parents,
                   "card": card, **_bench_env_config()},
        "results": results,
        "family_score_max_abs_diff": score_diff,
        "chowliu_edge_f1": cl_f1,
        "hillclimb_skeleton_f1": hc_f1,
    }
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    print(f"wrote {out}: {len(fams)} families "
          f"({results[0]['families_per_s']:.0f} fam/s einsum, "
          f"{results[1]['families_per_s']:.0f} pallas, "
          f"diff {score_diff:.2e}); chowliu F1={cl_f1:.2f}, "
          f"hillclimb F1={hc_f1:.2f} in {dt:.2f}s")
    return payload


def validate_bench_structure(payload: dict) -> None:
    """Schema gate for BENCH_structure.json — used by scripts/ci.sh."""
    for key, typ in BENCH_STRUCTURE_SCHEMA.items():
        if key not in payload:
            raise ValueError(f"BENCH_structure.json missing key {key!r}")
        if typ is float and isinstance(payload[key], int):
            continue
        if not isinstance(payload[key], typ):
            raise ValueError(f"{key!r} must be {typ.__name__}, "
                             f"got {type(payload[key]).__name__}")
    for key in ("jax_version", "pallas_policy"):
        if key not in payload["config"]:
            raise ValueError(f"config missing {key!r}")
    drivers = {r["driver"] for r in payload["results"]}
    for need in ("family_scores", "chowliu", "hillclimb"):
        if need not in drivers:
            raise ValueError(f"missing driver {need!r}")
    backends = {r["backend"] for r in payload["results"]
                if r["driver"] == "family_scores"}
    if backends != {"einsum", "pallas"}:
        raise ValueError(f"family_scores must cover both backends, "
                         f"got {backends}")
    if not payload["family_score_max_abs_diff"] < 1e-2:
        raise ValueError(
            "family_counts kernel diverged from the einsum reference: "
            f"{payload['family_score_max_abs_diff']}")
    if not payload["chowliu_edge_f1"] >= 0.99:
        raise ValueError(
            f"Chow-Liu tree recovery broke: F1={payload['chowliu_edge_f1']}")
    if not payload["hillclimb_skeleton_f1"] >= 0.7:
        raise ValueError("hill-climb skeleton recovery broke: "
                         f"F1={payload['hillclimb_skeleton_f1']}")


def bench_temporal_json(b: int = 512, t: int = 64, states: int = 3,
                        f: int = 2, sweeps: int = 5, chains: int = 2,
                        reps: int = 3, out: str = "BENCH_temporal.json"
                        ) -> dict:
    """(JSON mode) the temporal hot path (pgm_models.dynamic).

    Part 1 — HMM VB-EM at B=``b`` sequences x T=``t`` steps: the seed-style
    host sweep loop (one device dispatch per E/M step) vs the fused
    whole-fit ``lax.scan`` (``fused=True``), sequences/s both ways plus the
    posterior max-abs-diff between the two drivers (``tol=0`` so both run
    exactly ``sweeps`` sweeps).

    Part 2 — factorial HMM chain-parallel sweep: ``einsum`` vs ``pallas``
    suff-stats backends (the ``clg_seq_suffstats`` kernel), sequences/s and
    the learnt-means max-abs-diff.

    Part 3 — program caching: refitting a FRESH same-shape model must NOT
    retrace the fused program (``dynamic.trace_counts``) — recorded as the
    ``retrace_free`` flag the CI gate asserts.
    """
    import datetime

    from repro.data.synthetic import hmm_sequences
    from repro.pgm_models import FactorialHMMModel, HiddenMarkovModel
    from repro.pgm_models import dynamic as dyn

    stream = hmm_sequences(s=b, t=t, states=states, f=f, seed=0)[0]
    batch = stream.collect()
    results = []

    def make():
        m = HiddenMarkovModel(stream.attributes, n_states=states, seed=0)
        m._warm_start(batch.xc)     # identical init for every driver
        return m

    # -- part 1: fused scan vs host sweep loop -------------------------------
    mf, mu = make(), make()
    mf.update_model(batch, sweeps=sweeps, tol=0.0, fused=True)
    mu.update_model(batch, sweeps=sweeps, tol=0.0, fused=False)
    parity = float(np.abs(np.asarray(mf.posterior.emis.m)
                          - np.asarray(mu.posterior.emis.m)).max())
    for name, fused in (("hmm_update_host_loop", False),
                        ("hmm_fit_fused_scan", True)):
        m = make()
        m.update_model(batch, sweeps=sweeps, tol=0.0, fused=fused)  # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            m.update_model(batch, sweeps=sweeps, tol=0.0, fused=fused)
        dt = (time.perf_counter() - t0) / reps
        results.append({
            "driver": name, "B": b, "T": t, "sweeps": sweeps,
            "us_per_fit": dt * 1e6, "seq_per_s": b / dt,
            "sweeps_per_s": sweeps / dt,
        })
    speedup = results[1]["seq_per_s"] / results[0]["seq_per_s"]

    # -- part 2: fHMM suff-stats backends ------------------------------------
    fmeans = {}
    for backend in ("einsum", "pallas"):
        fm = FactorialHMMModel(stream.attributes, n_chains=chains,
                               n_states=2, seed=0)
        fm.update_model(batch, sweeps=sweeps, tol=0.0, backend=backend)
        fmeans[backend] = np.asarray(fm.means)
        t0 = time.perf_counter()
        for _ in range(reps):
            fm.update_model(batch, sweeps=sweeps, tol=0.0, backend=backend)
        dt = (time.perf_counter() - t0) / reps
        results.append({
            "driver": "fhmm_fit_fused_scan", "backend": backend,
            "B": b, "T": t, "sweeps": sweeps, "us_per_fit": dt * 1e6,
            "seq_per_s": b / dt, "sweeps_per_s": sweeps / dt,
        })
    fhmm_diff = float(np.abs(fmeans["einsum"] - fmeans["pallas"]).max())

    # -- part 3: a fresh same-shape model reuses the compiled program --------
    before = dyn.trace_counts().get("hmm_fit", 0)
    make().update_model(batch, sweeps=sweeps, tol=0.0, fused=True)
    retrace_free = dyn.trace_counts().get("hmm_fit", 0) == before

    payload = {
        "bench": "temporal",
        "schema_version": 1,
        "created": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "config": {"B": b, "T": t, "states": states, "features": f,
                   "sweeps": sweeps, "chains": chains,
                   **_bench_env_config()},
        "results": results,
        "speedup_seq_per_s": speedup,
        "fused_posterior_max_abs_diff": parity,
        "fhmm_backend_max_abs_diff": fhmm_diff,
        "retrace_free": retrace_free,
    }
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    print(f"wrote {out}: hmm_fit_fused_scan {speedup:.2f}x seq/s vs host "
          f"loop ({results[1]['seq_per_s']:.0f} vs "
          f"{results[0]['seq_per_s']:.0f}); posterior diff {parity:.2e}, "
          f"fhmm backend diff {fhmm_diff:.2e}, retrace_free={retrace_free}")
    return payload


def validate_bench_temporal(payload: dict) -> None:
    """Schema gate for BENCH_temporal.json — used by scripts/ci.sh."""
    for key, typ in BENCH_TEMPORAL_SCHEMA.items():
        if key not in payload:
            raise ValueError(f"BENCH_temporal.json missing key {key!r}")
        if typ is float and isinstance(payload[key], int):
            continue
        if not isinstance(payload[key], typ):
            raise ValueError(f"{key!r} must be {typ.__name__}, "
                             f"got {type(payload[key]).__name__}")
    for key in ("jax_version", "pallas_policy"):
        if key not in payload["config"]:
            raise ValueError(f"config missing {key!r}")
    drivers = {r["driver"] for r in payload["results"]}
    for need in ("hmm_update_host_loop", "hmm_fit_fused_scan",
                 "fhmm_fit_fused_scan"):
        if need not in drivers:
            raise ValueError(f"missing driver {need!r}")
    backends = {r.get("backend") for r in payload["results"]
                if r["driver"] == "fhmm_fit_fused_scan"}
    if backends != {"einsum", "pallas"}:
        raise ValueError(f"fhmm_fit_fused_scan must cover both backends, "
                         f"got {backends}")
    for r in payload["results"]:
        if not r["seq_per_s"] > 0:
            raise ValueError("seq_per_s must be positive")
    if not payload["speedup_seq_per_s"] > 1.0:
        raise ValueError("fused temporal fit must beat the host sweep loop: "
                         f"speedup {payload['speedup_seq_per_s']}")
    if not payload["fused_posterior_max_abs_diff"] < 1e-2:
        raise ValueError("fused/unfused posterior parity broke: "
                         f"{payload['fused_posterior_max_abs_diff']}")
    if not payload["fhmm_backend_max_abs_diff"] < 1e-2:
        raise ValueError("fHMM pallas backend diverged from einsum: "
                         f"{payload['fhmm_backend_max_abs_diff']}")
    if payload["retrace_free"] is not True:
        raise ValueError("same-shape refit retraced the fused program")


def _serve_offered_load(server, xs, load: float, duration: float,
                        deadline_ms: float, seed: int = 0,
                        swap_fn=None) -> dict:
    """Drive one offered-load window: Poisson arrivals at ``load`` q/s for
    ``duration`` s; optional hot swap at the halfway point.  Returns
    request-level latency stats (all tickets are awaited — a lost request
    would hang the bench, so completion IS the zero-drop check)."""
    rng = np.random.default_rng(seed)
    tickets = []
    swapped = swap_fn is None
    t0 = time.monotonic()
    end = t0 + duration
    F = xs.shape[1]
    while time.monotonic() < end:
        row = xs[rng.integers(len(xs))]
        tickets.append(server.submit(
            "Z", {f"X{i}": float(row[i]) for i in range(F)},
            deadline_ms=deadline_ms))
        if not swapped and time.monotonic() - t0 > duration / 2:
            swap_fn()
            swapped = True
        time.sleep(rng.exponential(1.0 / load))
    for t in tickets:
        t.result(timeout=120)
    dt = time.monotonic() - t0
    lat_ms = np.array([(t.done_s - t.submitted_s) * 1e3 for t in tickets])
    return {
        "offered_qps": load,
        "achieved_qps": len(tickets) / dt,
        "n_queries": len(tickets),
        "p50_ms": float(np.percentile(lat_ms, 50)),
        "p99_ms": float(np.percentile(lat_ms, 99)),
        "deadline_ms": deadline_ms,
        "deadline_misses": sum(t.deadline_miss for t in tickets),
        "swapped": swap_fn is not None,
    }


def bench_serve_json(duration: float = 3.0, loads: tuple = (200.0, 800.0),
                     deadline_ms: float = 50.0, max_batch: int = 32,
                     max_delay_ms: float = 5.0, n: int = 512, k: int = 3,
                     f: int = 4, out: str = "BENCH_serve.json") -> dict:
    """(JSON mode) the async serving tier (``repro.serve.queue``).

    A fitted GaussianMixture serves q(Z | x) queries (``mode="vmp"`` — the
    jitted ``posterior_z`` path) through :class:`AsyncPGMServer` under
    Poisson offered load, at each load in ``loads``, for two drivers:

    * ``serve_single`` — one engine replica, plain single-device dispatch;
      the FIRST load window includes a mid-stream hot model swap, and the
      bench blocks on every ticket — completion of all of them is the
      zero-drop check recorded as ``hot_swap_zero_drop``.
    * ``serve_mesh`` — the same buckets data-sharded across all visible
      devices via the ``dvmp`` ``shard_map`` path (run under
      ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for a real
      mesh on CPU).

    Request-level p50/p99 come from ticket submit->done wall times;
    bucket-level p50/p99 are aggregated from the ``serve_bucket``
    ``latency_us`` telemetry (obs JSONL), per the ROADMAP serving item.
    """
    import datetime
    import os
    import tempfile

    import jax

    from repro import obs
    from repro.launch.mesh import make_mesh
    from repro.data.synthetic import gmm_stream
    from repro.pgm_models import GaussianMixture
    from repro.serve.queue import AsyncPGMServer

    stream, _, _ = gmm_stream(n, k, f, seed=0)
    model = GaussianMixture(stream.attributes, n_states=k)
    model.update_model(stream)
    xs = np.asarray(stream.collect().xc)
    ndev = len(jax.devices())
    mesh = make_mesh((ndev,), ("data",))

    results = []
    hit_rates = []
    zero_drop = False
    for driver in ("serve_single", "serve_mesh"):
        for li, load in enumerate(loads):
            tmp = tempfile.NamedTemporaryFile(
                suffix=".jsonl", delete=False).name
            server = AsyncPGMServer(
                model, mode="vmp", max_batch=max_batch,
                max_delay_ms=max_delay_ms, default_deadline_ms=deadline_ms,
                mesh=mesh if driver == "serve_mesh" else None)
            prev = None
            try:
                # warm the plan cache BEFORE enabling telemetry, so compile
                # latencies stay out of the measured bucket percentiles —
                # one plan per pow2 batch capacity the load will coalesce to
                cap = 1
                while cap <= 2 * max_batch:
                    warm = [server.submit(
                        "Z", {f"X{i}": float(xs[j % len(xs), i])
                              for i in range(f)})
                        for j in range(cap)]
                    for t in warm:
                        t.result(timeout=120)
                    cap *= 2
                prev = obs.configure(level="basic", path=tmp)

                swap_fn = None
                swap_thread = []
                if driver == "serve_single" and li == 0:
                    import threading

                    refit = GaussianMixture(stream.attributes, n_states=k,
                                            seed=1)
                    refit.update_model(stream)

                    def swap_fn():
                        # swap from a side thread: arrivals keep flowing
                        # while the new version warms in the background
                        th = threading.Thread(
                            target=server.swap_model, args=(refit,))
                        th.start()
                        swap_thread.append(th)

                row = _serve_offered_load(server, xs, load, duration,
                                          deadline_ms, seed=li,
                                          swap_fn=swap_fn)
                for th in swap_thread:
                    th.join()
                if swap_fn is not None:
                    # every ticket resolved across the swap -> zero dropped
                    zero_drop = (server.stats()["pending"] == 0)
            finally:
                server.stop()
                if prev is not None:
                    obs.configure(**prev)
            st = server.stats()
            hit_rates.append(st["plans"]["hit_rate"])
            bucket_us = [e["latency_us"] for e in
                         (json.loads(l) for l in open(tmp))
                         if e["event"] == "serve_bucket"]
            os.unlink(tmp)
            row.update({
                "driver": driver,
                "n_devices": ndev if driver == "serve_mesh" else 1,
                "bucket_p50_us": float(np.percentile(bucket_us, 50)),
                "bucket_p99_us": float(np.percentile(bucket_us, 99)),
                "n_buckets": len(bucket_us),
                "plan_cache_hit_rate": st["plans"]["hit_rate"],
                "flushes": st["flushes"],
            })
            results.append(row)

    payload = {
        "bench": "serve",
        "schema_version": 1,
        "created": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "config": {"duration_s": duration, "loads_qps": list(loads),
                   "deadline_ms": deadline_ms, "max_batch": max_batch,
                   "max_delay_ms": max_delay_ms, "n": n, "components": k,
                   "features": f, "mode": "vmp", "n_devices": ndev,
                   **_bench_env_config()},
        "results": results,
        "plan_cache_hit_rate": float(np.mean(hit_rates)),
        "hot_swap_zero_drop": zero_drop,
    }
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    r0 = results[0]
    print(f"wrote {out}: serve_single {r0['achieved_qps']:.0f} q/s at "
          f"{r0['offered_qps']:.0f} offered (p50 {r0['p50_ms']:.1f}ms, "
          f"p99 {r0['p99_ms']:.1f}ms), mesh x{ndev}, plan hit-rate "
          f"{payload['plan_cache_hit_rate']:.2f}, "
          f"hot_swap_zero_drop={zero_drop}")
    return payload


def validate_bench_serve(payload: dict) -> None:
    """Schema gate for BENCH_serve.json — used by scripts/ci.sh."""
    for key, typ in BENCH_SERVE_SCHEMA.items():
        if key not in payload:
            raise ValueError(f"BENCH_serve.json missing key {key!r}")
        if typ is float and isinstance(payload[key], int):
            continue
        if not isinstance(payload[key], typ):
            raise ValueError(f"{key!r} must be {typ.__name__}, "
                             f"got {type(payload[key]).__name__}")
    for key in ("jax_version", "pallas_policy"):
        if key not in payload["config"]:
            raise ValueError(f"config missing {key!r}")
    drivers = {r["driver"] for r in payload["results"]}
    if drivers != {"serve_single", "serve_mesh"}:
        raise ValueError(f"unexpected drivers {drivers}")
    for need in drivers:
        loads = {r["offered_qps"] for r in payload["results"]
                 if r["driver"] == need}
        if len(loads) < 2:
            raise ValueError(f"driver {need!r} must cover >= 2 offered "
                             f"loads, got {sorted(loads)}")
    for r in payload["results"]:
        for field in ("offered_qps", "achieved_qps", "n_queries", "p50_ms",
                      "p99_ms", "bucket_p50_us", "bucket_p99_us",
                      "deadline_misses", "n_devices",
                      "plan_cache_hit_rate"):
            if field not in r:
                raise ValueError(f"result {r['driver']} missing {field!r}")
        if not r["achieved_qps"] > 0:
            raise ValueError("achieved_qps must be positive")
        if r["p99_ms"] < r["p50_ms"]:
            raise ValueError("p99 below p50 — latency aggregation broken")
    if not 0.0 <= payload["plan_cache_hit_rate"] <= 1.0:
        raise ValueError("plan_cache_hit_rate out of [0, 1]")
    if payload["hot_swap_zero_drop"] is not True:
        raise ValueError("hot swap dropped requests (or never ran)")


def _tree_bit_equal(a, b) -> bool:
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def bench_resilience_json(n: int = 50_000, batch: int = 2_000,
                          sweeps: int = 5, k: int = 3, f: int = 8,
                          poison_rate: float = 0.01,
                          duration: float = 2.0, load: float = 300.0,
                          out: str = "BENCH_resilience.json") -> dict:
    """(JSON mode) the fault-tolerance layer under injected faults.

    Three legs, each comparing a clean run against the same run with
    seeded faults from :class:`repro.resilience.FaultInjector`:

    * **streaming** — the fused ``stream_fit`` scan over a clean stream vs
      the same stream with ``poison_rate`` of its batches NaN-poisoned.
      Records inst/s for both (quarantine is a held-state select inside
      the compiled scan, so the overhead should be noise) and asserts the
      quarantine bit-identity: the poisoned run's final posterior equals a
      run that never saw the poisoned batches.
    * **serving** — ``AsyncPGMServer`` (2 replicas, vmp mode) under
      Poisson offered load, clean vs a run with one worker crash and one
      transient plan-compile failure injected mid-stream.  Records
      achieved qps / p50 / p99 for both, the restart/retry counters, and
      the zero-loss flag (every accepted ticket resolves; pending == 0).
    * **checkpoint** — snapshot the full streaming state mid-stream, then
      time crash recovery: restore from disk + replay the tail, with the
      bit-identical-resume flag against the uninterrupted run.
    """
    import datetime
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.core import streaming, vmp
    from repro.core.dag import PlateSpec
    from repro.data.synthetic import gmm_stream
    from repro.pgm_models import GaussianMixture
    from repro.resilience import CheckpointManager, FaultInjector
    from repro.resilience import checkpoint as rckpt
    from repro.serve.plan import PlanCache
    from repro.serve.queue import AsyncPGMServer

    backend = vmp.default_backend()
    spec = PlateSpec(n_features=f, latent_card=k)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    stream, _, _ = gmm_stream(n, k, f, seed=0)
    batches = list(stream.batches(batch))
    nb = len(batches)
    xcs = jnp.stack([b.xc for b in batches])
    xds = jnp.stack([b.xd for b in batches])

    # -- streaming under NaN poison -------------------------------------------
    inj = FaultInjector(seed=0)
    bad, idx = inj.poison_nan(np.asarray(xcs), rate=poison_rate)
    bad = jnp.asarray(bad)

    def run(x, d):
        ss = streaming.stream_init(prior, init)
        ss, _ = streaming.stream_fit(cp, prior, ss, x, d, sweeps=sweeps,
                                     backend=backend)
        jax.block_until_ready(ss.post.reg.m)
        return ss

    run(xcs, xds)                                     # warm the scan
    t0 = time.perf_counter()
    clean_state = run(xcs, xds)
    clean_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    poisoned_state = run(bad, xds)
    poison_dt = time.perf_counter() - t0
    keep = np.setdiff1d(np.arange(nb), idx)
    never_state = run(xcs[keep], xds[keep])
    bit_identical = _tree_bit_equal(poisoned_state.post, never_state.post)
    streaming_leg = {
        "n_batches": nb, "n_poisoned": int(len(idx)),
        "quarantined": int(poisoned_state.n_quarantined),
        "clean_inst_per_s": n / clean_dt,
        "poisoned_inst_per_s": n / poison_dt,
        "overhead_pct": (poison_dt / clean_dt - 1.0) * 100.0,
    }

    # -- serving through a crash + compile failure ----------------------------
    model = GaussianMixture(stream.attributes, n_states=k)
    model.update_model(stream)
    xs = np.asarray(stream.collect().xc)

    def serve_leg(faults: bool) -> dict:
        cache = PlanCache(compile_retries=2, retry_backoff_s=0.01)
        inj = FaultInjector(seed=1)
        with AsyncPGMServer(model, mode="vmp", max_batch=32,
                            max_delay_ms=5.0, default_deadline_ms=60_000,
                            replicas=2, plan_cache=cache,
                            supervise_interval_ms=5) as srv:
            cap = 1                                   # warm pow2 plans
            while cap <= 64:
                if faults and cap == 64:
                    # the last warm compile hits the injected failure and
                    # must retry — deterministic, and it keeps the compile
                    # fault out of the measured load window
                    inj.fail_compiles(cache, n=1)
                warm = [srv.submit("Z", {f"X{i}": float(xs[j % len(xs), i])
                                         for i in range(f)})
                        for j in range(cap)]
                for t in warm:
                    t.result(timeout=120)
                cap *= 2
            if faults:
                inj.crash_worker(srv)                 # any worker, mid-load
            row = _serve_offered_load(srv, xs, load, duration,
                                      deadline_ms=60_000, seed=2)
            st = srv.stats()
        return {
            "achieved_qps": row["achieved_qps"], "p50_ms": row["p50_ms"],
            "p99_ms": row["p99_ms"], "n_queries": row["n_queries"],
            "worker_restarts": st["worker_restarts"],
            "compile_retries": st["plans"]["retries"], "shed": st["shed"],
            "lost_tickets": st["pending"],
        }

    clean_serve = serve_leg(faults=False)
    faulted_serve = serve_leg(faults=True)
    zero_loss = (faulted_serve["lost_tickets"] == 0
                 and faulted_serve["worker_restarts"] >= 1
                 and faulted_serve["compile_retries"] >= 1)

    # -- checkpoint save / restore / recovery ---------------------------------
    half = nb // 2
    with tempfile.TemporaryDirectory() as ckdir:
        mgr = CheckpointManager(ckdir, every=0, keep=2)
        head = run(xcs[:half], xds[:half])
        t0 = time.perf_counter()
        mgr.save(half, head)
        save_ms = (time.perf_counter() - t0) * 1e3
        t0 = time.perf_counter()
        restored, meta = rckpt.load(mgr.latest(),
                                    streaming.stream_init(prior, init))
        restore_ms = (time.perf_counter() - t0) * 1e3
        t0 = time.perf_counter()
        resumed, _ = rckpt.resume_stream_fit(
            cp, prior, streaming.stream_init(prior, init), xcs, xds,
            manager=mgr, sweeps=sweeps, backend=backend)
        recovery_s = time.perf_counter() - t0
    resume_ok = _tree_bit_equal(resumed, clean_state)
    checkpoint_leg = {
        "save_ms": save_ms, "restore_ms": restore_ms,
        "recovery_s": recovery_s, "resumed_batches": nb - half,
        "checkpoint_t": int(meta["t"]),
    }

    payload = {
        "bench": "resilience",
        "schema_version": 1,
        "created": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "config": {"n": n, "batch": batch, "sweeps": sweeps, "features": f,
                   "components": k, "poison_rate": poison_rate,
                   "duration_s": duration, "load_qps": load,
                   "backend": backend, **_bench_env_config()},
        "streaming": streaming_leg,
        "serving": {"clean": clean_serve, "faulted": faulted_serve},
        "checkpoint": checkpoint_leg,
        "quarantine_bit_identical": bit_identical,
        "serve_zero_loss": zero_loss,
        "resume_bit_identical": resume_ok,
    }
    with open(out, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    print(f"wrote {out}: poisoned stream {streaming_leg['poisoned_inst_per_s']:.0f} "
          f"inst/s vs clean {streaming_leg['clean_inst_per_s']:.0f} "
          f"({streaming_leg['quarantined']} batches quarantined, "
          f"bit_identical={bit_identical}); faulted serve "
          f"{faulted_serve['achieved_qps']:.0f} q/s p99 "
          f"{faulted_serve['p99_ms']:.1f}ms vs clean "
          f"{clean_serve['achieved_qps']:.0f} q/s "
          f"(restarts={faulted_serve['worker_restarts']}, zero_loss="
          f"{zero_loss}); recovery {checkpoint_leg['recovery_s']:.2f}s "
          f"resume_bit_identical={resume_ok}")
    return payload


def validate_bench_resilience(payload: dict) -> None:
    """Schema + invariant gate for BENCH_resilience.json (scripts/ci.sh)."""
    for key, typ in BENCH_RESILIENCE_SCHEMA.items():
        if key not in payload:
            raise ValueError(f"BENCH_resilience.json missing key {key!r}")
        if typ is float and isinstance(payload[key], int):
            continue
        if not isinstance(payload[key], typ):
            raise ValueError(f"{key!r} must be {typ.__name__}, "
                             f"got {type(payload[key]).__name__}")
    for key in ("jax_version", "pallas_policy"):
        if key not in payload["config"]:
            raise ValueError(f"config missing {key!r}")
    s = payload["streaming"]
    if not (s["clean_inst_per_s"] > 0 and s["poisoned_inst_per_s"] > 0):
        raise ValueError("streaming throughput must be positive")
    if s["n_poisoned"] < 1 or s["quarantined"] != s["n_poisoned"]:
        raise ValueError(f"quarantine miscount: {s['quarantined']} flagged "
                         f"vs {s['n_poisoned']} poisoned")
    if payload["quarantine_bit_identical"] is not True:
        raise ValueError("poisoned-run posterior diverged from the "
                         "never-poisoned run")
    for leg in ("clean", "faulted"):
        r = payload["serving"][leg]
        if not r["achieved_qps"] > 0:
            raise ValueError(f"{leg} serving qps must be positive")
        if r["p99_ms"] < r["p50_ms"]:
            raise ValueError("p99 below p50 — latency aggregation broken")
    fr = payload["serving"]["faulted"]
    if fr["lost_tickets"] != 0:
        raise ValueError(f"faulted serve lost {fr['lost_tickets']} tickets")
    if fr["worker_restarts"] < 1 or fr["compile_retries"] < 1:
        raise ValueError("faults did not fire (no restart / no retry) — "
                         "the faulted leg measured nothing")
    if payload["serve_zero_loss"] is not True:
        raise ValueError("serve_zero_loss flag is false")
    c = payload["checkpoint"]
    if not (c["save_ms"] > 0 and c["restore_ms"] > 0
            and c["recovery_s"] > 0):
        raise ValueError("checkpoint timings must be positive")
    if payload["resume_bit_identical"] is not True:
        raise ValueError("mid-stream resume diverged from the "
                         "uninterrupted run")


def bench_drift():
    """(iv) drift detection latency (batches until flagged)."""
    import jax

    from repro.core import streaming, vmp
    from repro.core.dag import PlateSpec
    from repro.data.synthetic import drift_stream

    stream, _ = drift_stream(2_500, 4, seed=1)
    spec = PlateSpec(n_features=4, latent_card=1)
    cp = vmp.compile_plate(spec)
    prior = vmp.default_prior(cp)
    ss = streaming.stream_init(
        prior, vmp.symmetry_broken(prior, jax.random.PRNGKey(0)))
    fired = -1
    for i, b in enumerate(stream.batches(250)):
        ss, info = streaming.stream_update(cp, prior, ss, b.xc, b.xd,
                                           drift_threshold=3.0)
        if bool(info["drifted"]) and fired < 0:
            fired = i
    print(f"drift_detection,0,fired_at_batch={fired} (shift at 10)")


def bench_model_zoo():
    """(v) Table-2 recovery metrics."""
    import itertools

    from repro.data import synthetic as syn
    from repro.pgm_models import (GaussianMixture, HiddenMarkovModel, LDA,
                                  NaiveBayesClassifier)

    s, means, _ = syn.gmm_stream(2000, 3, 4, seed=1)
    m = GaussianMixture(s.attributes, n_states=3)
    t0 = time.perf_counter()
    m.update_model(s)
    gmm_t = time.perf_counter() - t0
    err = float(np.abs(np.sort(np.asarray(m.posterior.reg.m[:, :, 0]).T, 0)
                       - np.sort(means, 0)).max())
    print(f"zoo_gmm_fit,{gmm_t * 1e6:.0f},mean_err={err:.3f}")

    s, y = syn.nb_stream(1500, 3, 2, 2, seed=2)
    clf = NaiveBayesClassifier(s.attributes)
    clf.update_model(s)
    acc = float((np.asarray(clf.predict(s)) == y).mean())
    print(f"zoo_nbc,0,acc={acc:.3f}")

    ds, trans, hm_means, zs = syn.hmm_sequences(20, 60, 3, 2, seed=6)
    hm = HiddenMarkovModel(ds.attributes, n_states=3, seed=1)
    hm.update_model(ds)
    vit = hm.viterbi_states(ds.collect().xc)
    acc = max((np.asarray(vit) == np.array(p)[zs].reshape(vit.shape)).mean()
              for p in itertools.permutations(range(3)))
    print(f"zoo_hmm,0,decode_acc={acc:.3f}")

    counts, beta = syn.lda_corpus(120, 50, 4, seed=8)
    lda = LDA(4, 50, seed=0)
    lda.update_model(counts, sweeps=25)
    score = max(sum(float(lda.topics()[p[t]] @ beta[t]) for t in range(4))
                for p in itertools.permutations(range(4)))
    print(f"zoo_lda,0,topic_score={score:.2f} (perfect~0.80, random~0.08)")


def bench_importance_sampling():
    """(vi) parallel IS throughput and effective sample size."""
    import jax.numpy as jnp

    from repro.core.dag import (BayesianNetwork, CLGCPD, DAG, MultinomialCPD,
                                Variables)
    from repro.core.importance_sampling import ImportanceSampling

    vs = Variables()
    Z = vs.new_multinomial("Z", 2)
    X1 = vs.new_gaussian("X1")
    X2 = vs.new_gaussian("X2")
    dag = DAG(vs)
    dag.add_parent(X1, Z)
    dag.add_parent(X2, Z)
    bn = BayesianNetwork(dag, {
        "Z": MultinomialCPD(jnp.array([0.3, 0.7])),
        "X1": CLGCPD(jnp.array([0.0, 4.0]), jnp.zeros((2, 0)),
                     jnp.array([1.0, 1.0])),
        "X2": CLGCPD(jnp.array([-2.0, 2.0]), jnp.zeros((2, 0)),
                     jnp.array([1.0, 1.0]))})
    inf = ImportanceSampling(n_samples=100_000, seed=0)
    inf.set_model(bn)
    inf.set_evidence({"X1": 3.0, "X2": 1.0})
    t0 = time.perf_counter()
    inf.run_inference()
    dt = time.perf_counter() - t0
    print(f"importance_sampling_100k,{dt * 1e6:.0f},"
          f"ESS={float(inf.effective_sample_size()):.0f}")


def bench_kernels():
    """(vii) kernel calls (interpret mode: correctness-grade timing)."""
    import jax

    from repro.kernels import ops

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 256, 4, 64))
    k = jax.random.normal(key, (1, 256, 2, 64))
    us = _t(ops.flash_attention, q, k, k, reps=2)
    print(f"kernel_flash_attn_256,{us:.0f},interpret-mode")
    x = jax.random.normal(key, (1, 128, 4, 32))
    dt = jax.nn.softplus(jax.random.normal(key, (1, 128, 4)))
    A = jax.numpy.ones((4,))
    B = jax.random.normal(key, (1, 128, 1, 32))
    us = _t(ops.ssd_scan, x, dt, A, B, B, chunk=32, reps=2)
    print(f"kernel_ssd_scan_128,{us:.0f},interpret-mode")
    d = jax.random.normal(key, (512, 2, 4))
    yv = jax.random.normal(key, (512, 2))
    r = jax.nn.softmax(jax.random.normal(key, (512, 3)), -1)
    us = _t(ops.clg_suffstats, d, yv, r, reps=2)
    print(f"kernel_clg_stats_512,{us:.0f},interpret-mode")


def bench_exact_vs_approx():
    """(ix) exact junction tree vs importance sampling vs VMP: marginal
    accuracy and query throughput (the infer_exact subsystem — the paper's
    HUGIN link, served natively)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.dag import (BayesianNetwork, CLGCPD, DAG,
                                MultinomialCPD, Variables)
    from repro.core.importance_sampling import ImportanceSampling
    from repro.data.synthetic import gmm_stream
    from repro.infer_exact import JunctionTreeEngine
    from repro.pgm_models import GaussianMixture

    # ground-truth CLG mixture Z -> X0..X3
    K, Fdim = 3, 4
    rng = np.random.RandomState(0)
    vs = Variables()
    Z = vs.new_multinomial("Z", K)
    xs = [vs.new_gaussian(f"X{f}") for f in range(Fdim)]
    dag = DAG(vs)
    for x in xs:
        dag.add_parent(x, Z)
    cpds = {"Z": MultinomialCPD(jnp.asarray(rng.dirichlet(np.ones(K))))}
    for f, x in enumerate(xs):
        cpds[x.name] = CLGCPD(jnp.asarray(rng.randn(K) * 3.0),
                              jnp.zeros((K, 0)),
                              jnp.ones(K))
    bn = BayesianNetwork(dag, cpds)
    B = 64
    sample = bn.sample(jax.random.PRNGKey(1), B)
    evidence = {x.name: sample[x.name] for x in xs}

    # junction tree: B queries, ONE batched device call
    jt = JunctionTreeEngine(bn)
    jt.set_evidence(evidence)
    jt.run_inference()  # compile
    t0 = time.perf_counter()
    for _ in range(3):
        jt.run_inference()
        exact = jt.posterior_discrete(Z)
    jax.block_until_ready(exact)
    dt = (time.perf_counter() - t0) / 3
    exact = np.asarray(exact)
    print(f"exact_vs_approx_jt,{dt / B * 1e6:.0f},{B / dt:.0f} q/s "
          f"(batched, err=0 oracle)")

    # importance sampling: one run per query instance
    n_is = 8
    t0 = time.perf_counter()
    is_err = 0.0
    for b in range(n_is):
        inf = ImportanceSampling(n_samples=20_000, seed=b)
        inf.set_model(bn)
        inf.set_evidence({x.name: float(sample[x.name][b]) for x in xs})
        inf.run_inference()
        is_err = max(is_err, float(np.abs(
            np.asarray(inf.posterior_discrete(Z)) - exact[b]).max()))
    dt = (time.perf_counter() - t0) / n_is
    print(f"exact_vs_approx_is20k,{dt * 1e6:.0f},{1 / dt:.1f} q/s "
          f"max_err={is_err:.4f}")

    # VMP: fit a GaussianMixture, compare its E-step posterior against the
    # junction tree run on the model's own BN export
    stream, _, _ = gmm_stream(2000, K, Fdim, seed=2)
    m = GaussianMixture(stream.attributes, n_states=K)
    m.update_model(stream)
    batch = stream.collect()
    t0 = time.perf_counter()
    rz = m.posterior_z(batch)
    jax.block_until_ready(rz)
    dt = time.perf_counter() - t0
    re = m.posterior_exact(batch)
    vmp_err = float(np.abs(np.asarray(rz) - np.asarray(re)).max())
    print(f"exact_vs_approx_vmp,{dt / batch.xc.shape[0] * 1e6:.2f},"
          f"{batch.xc.shape[0] / dt:.0f} q/s max_err={vmp_err:.2e} "
          f"(vs jt on exported BN)")


def bench_lm_training():
    """(viii) reduced-config LM training throughput."""
    import jax

    from repro.configs import get_config
    from repro.data.tokens import TokenStream, markov_sequence_fast
    from repro.nn import transformer as T
    from repro.train import optimizer as opt
    from repro.train import step as ts

    cfg = get_config("granite-3-2b").reduced()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    state = ts.init_train_state(params)
    toks = markov_sequence_fast(20_000, cfg.vocab, seed=1)
    stream = TokenStream(toks, batch=8, seq=128)
    lr_fn = opt.cosine_schedule(1e-3, 10, 100)
    jstep = jax.jit(partial(ts.train_step, cfg=cfg, lr_fn=lr_fn))
    batches = list(stream.batches(12))
    state, _ = jstep(state, batches[0])  # compile
    t0 = time.perf_counter()
    for b in batches[1:]:
        state, m = jstep(state, b)
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    tps = 11 * 8 * 128 / dt
    print(f"lm_train_step,{dt / 11 * 1e6:.0f},{tps:.0f} tok/s "
          f"loss={float(m['loss']):.3f}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="run the streaming before/after comparison and "
                         "write BENCH_streaming.json instead of CSV rows")
    ap.add_argument("--dvmp", action="store_true",
                    help="with --json: run the d-VMP mesh-path driver and "
                         "write BENCH_dvmp.json instead")
    ap.add_argument("--latent", action="store_true",
                    help="with --json: run the latent-plate E-step + "
                         "bucketed strong-JT drivers and write "
                         "BENCH_latent.json instead")
    ap.add_argument("--structure", action="store_true",
                    help="with --json: run the structure-learning drivers "
                         "(family scoring, Chow-Liu, hill-climb) and write "
                         "BENCH_structure.json instead")
    ap.add_argument("--temporal", action="store_true",
                    help="with --json: run the fused temporal VB-EM drivers "
                         "(HMM scan vs host loop, fHMM backends) and write "
                         "BENCH_temporal.json instead")
    ap.add_argument("--serve", action="store_true",
                    help="with --json: drive the async serving tier under "
                         "Poisson offered load (single-device vs mesh "
                         "replicas) and write BENCH_serve.json instead")
    ap.add_argument("--resilience", action="store_true",
                    help="with --json: run the fault-injection drivers "
                         "(NaN-poisoned stream, worker crash + compile "
                         "failure under load, checkpoint recovery) and "
                         "write BENCH_resilience.json instead")
    ap.add_argument("--out", default=None)
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--batch", type=int, default=2_000)
    ap.add_argument("--sweeps", type=int, default=5)
    ap.add_argument("--window", type=int, default=5,
                    help="stream_fit_windowed driver's device-resident "
                         "window (batches)")
    ap.add_argument("--devices", type=int, default=0,
                    help="mesh size for --dvmp (default: all jax devices)")
    ap.add_argument("--backend", default=None,
                    help="suff-stats backend for stream_fit "
                         "(einsum|pallas; default: auto)")
    ap.add_argument("--latent-n", type=int, default=8_192,
                    help="instances for the --latent E-step drivers")
    ap.add_argument("--depth", type=int, default=12,
                    help="CLG chain depth for the --latent strong-JT driver")
    ap.add_argument("--structure-n", type=int, default=20_000,
                    help="instances for the --structure drivers")
    ap.add_argument("--structure-vars", type=int, default=8,
                    help="variables for the --structure drivers")
    ap.add_argument("--temporal-b", type=int, default=512,
                    help="sequences per batch for the --temporal drivers")
    ap.add_argument("--temporal-t", type=int, default=64,
                    help="steps per sequence for the --temporal drivers")
    ap.add_argument("--serve-duration", type=float, default=3.0,
                    help="offered-load window per --serve config, seconds")
    ap.add_argument("--serve-loads", type=float, nargs="+",
                    default=[200.0, 800.0],
                    help="offered loads (queries/s) for the --serve drivers")
    ap.add_argument("--deadline-ms", type=float, default=50.0,
                    help="per-request deadline for the --serve drivers")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the benchmark "
                         "run into DIR (open with TensorBoard/Perfetto)")
    args = ap.parse_args(argv)
    from repro.launch.cache import use_compile_cache

    use_compile_cache()

    if ((args.dvmp or args.latent or args.structure or args.temporal
         or args.serve or args.resilience) and not args.json):
        ap.error("--dvmp/--latent/--structure/--temporal/--serve/"
                 "--resilience require --json (they write BENCH_*.json)")

    from repro.obs.profile import profile

    with profile(args.profile):
        if args.json and args.dvmp:
            payload = bench_dvmp_json(
                n=args.n, sweeps=args.sweeps, backend=args.backend,
                n_devices=args.devices, out=args.out or "BENCH_dvmp.json")
            validate_bench_dvmp(payload)
            return
        if args.json and args.latent:
            payload = bench_latent_json(
                n=args.latent_n, depth=args.depth,
                out=args.out or "BENCH_latent.json")
            validate_bench_latent(payload)
            return
        if args.json and args.structure:
            payload = bench_structure_json(
                n=args.structure_n, n_vars=args.structure_vars,
                out=args.out or "BENCH_structure.json")
            validate_bench_structure(payload)
            return
        if args.json and args.temporal:
            payload = bench_temporal_json(
                b=args.temporal_b, t=args.temporal_t, sweeps=args.sweeps,
                out=args.out or "BENCH_temporal.json")
            validate_bench_temporal(payload)
            return
        if args.json and args.serve:
            payload = bench_serve_json(
                duration=args.serve_duration, loads=tuple(args.serve_loads),
                deadline_ms=args.deadline_ms,
                out=args.out or "BENCH_serve.json")
            validate_bench_serve(payload)
            return
        if args.json and args.resilience:
            payload = bench_resilience_json(
                n=args.n, batch=args.batch, sweeps=args.sweeps,
                duration=args.serve_duration,
                out=args.out or "BENCH_resilience.json")
            validate_bench_resilience(payload)
            return
        if args.json:
            payload = bench_streaming_json(
                n=args.n, batch=args.batch, sweeps=args.sweeps,
                backend=args.backend, window=args.window,
                out=args.out or "BENCH_streaming.json")
            validate_bench_streaming(payload)
            return

        print("name,us_per_call,derived")
        for fn in (bench_vmp_parallel, bench_streaming, bench_drift,
                   bench_model_zoo, bench_importance_sampling, bench_kernels,
                   bench_exact_vs_approx, bench_lm_training):
            fn()


if __name__ == "__main__":
    main()
