#!/usr/bin/env python3
"""Bring-up smoke of the main path on a TPU, through the user entry points.

One chip (no arguments), one process, every phase:

1. ``gmm_large`` (10 features, 4 components) data from ``--seed``:
   16 equal batches of 2^20 instances.
2. Streaming Bayesian learning: ``GaussianMixture.update_model(DataStream)``
   -> the ``stream_fit`` scan, with the suff-stats reduction in the compiled
   Pallas kernels (checked: ``tpu_custom_call`` in the compiled scan).
3. The posterior against a plain reference on the same data (the einsum
   backend at ``highest`` matmul precision) and against the generator's
   true means.
4. Query serving through ``AsyncPGMServer``: ``mode="vmp"`` on the fitted
   model against direct ``posterior_z``, and ``mode="exact"`` on a
   ``random_discrete_bn`` against brute-force enumeration.

``--chips 4`` runs only d-VMP across four chips: ``update_model(batch,
mesh=...)`` on 2^24 instances placed with a ``NamedSharding``, and the
single-device fit of the same data it is compared with.

Earlier lines report what is worth knowing (compile seconds, sizes, peak
device memory, the error of each comparison); none of it is a
measurement.  The last line is one JSON object, ``{"ok": true, "device":
{...}}``.  Any failed phase raises and exits non-zero; without a TPU the
script exits non-zero before any phase.

Run: ``python3 chip_smoke.py [--seed 0] [--chips 4]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

GMM = "gmm_large"
NOISE = 0.7          # per-feature standard deviation of the generated data


def log(msg: str) -> None:
    print(msg, flush=True)


def check(name: str, err: float, tol: float, why: str) -> None:
    """One reference comparison: print it, raise if it is out of bounds."""
    ok = bool(err <= tol)
    log(f"  {'PASS' if ok else 'FAIL'} {name}: error {err:.3e} <= tol "
        f"{tol:.1e}  ({why})")
    if not ok:
        raise AssertionError(f"{name}: error {err:.3e} > tol {tol:.1e}")


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def gmm_data(n: int, seed: int):
    """``gmm_large`` instances from the synthetic generator: (x, means)."""
    from repro.configs.amidst_pgm import PGM_WORKLOADS
    from repro.data.synthetic import gmm_stream

    spec = PGM_WORKLOADS[GMM].spec
    stream, means, _ = gmm_stream(n, spec.latent_card, spec.n_features,
                                  seed=seed, noise=NOISE)
    x, _ = next(stream.chunks())
    return stream.attributes, x, means


def posterior_summary(model):
    """(mixture counts [K], component means [F, K], variances [F, K])."""
    p = model.posterior
    return (np.asarray(p.mix.alpha, np.float64),
            np.asarray(p.reg.m[..., 0], np.float64),
            np.asarray(p.reg.b / p.reg.a, np.float64))


def compare_posteriors(got, ref, label: str, count_tol: float,
                       mean_tol: float, why: str) -> None:
    """Relative errors of counts and means, and of the variances, whose
    ``sum y^2 - n mean^2`` cancels ~35-fold (|mean| <= 4, noise 0.7) and so
    amplifies any f32 sum error: their bound is 10x the means'."""
    for name, a, b, tol in (("counts", got[0], ref[0], count_tol),
                            ("means", got[1], ref[1], mean_tol),
                            ("variances", got[2], ref[2], 10 * mean_tol)):
        rel = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))
        check(f"{label} {name}", rel, tol, why)


def check_true_means(label: str, fitted_means: np.ndarray,
                     true_means: np.ndarray, n: int) -> None:
    """Pair each true component with its nearest fitted one (the labels of
    a mixture are arbitrary) and bound the largest coordinate error."""
    fm = fitted_means.T                                    # [K, F]
    d = ((true_means[:, None, :] - fm[None]) ** 2).sum(-1)
    pair = d.argmin(1)
    if len(set(pair.tolist())) != len(pair):
        raise AssertionError(f"two true components share fitted component "
                             f"{pair.tolist()}: the fit merged clusters")
    se = NOISE / np.sqrt(n / len(true_means))
    check(label, float(np.abs(true_means - fm[pair]).max()), 10 * se,
          why=f"standard error of a component mean over {n} instances is "
              f"{se:.1e}; tol = 10 of them")


# ---------------------------------------------------------------------------
# one chip: streaming VMP + serving
# ---------------------------------------------------------------------------


def stream_phase(seed: int, n_batches: int, batch: int):
    """Fit ``gmm_large`` through ``update_model(DataStream)``; returns the
    model, the data and the stream."""
    from repro.data.stream import DataStream
    from repro.pgm_models.static import GaussianMixture

    t0 = time.perf_counter()
    attrs, x, means = gmm_data(n_batches * batch, seed)
    xd = np.zeros((batch, 0), np.int32)
    stream = DataStream(
        attrs, lambda: ((x[i * batch:(i + 1) * batch], xd)
                        for i in range(n_batches)),
        n_instances=len(x))
    log(f"data: {n_batches} batches x {batch} instances x {x.shape[1]} "
        f"features ({x[:batch].nbytes / 1e6:.1f} MB per batch), generated "
        f"in {time.perf_counter() - t0:.1f} s")
    model = GaussianMixture(attrs, n_states=len(means), seed=seed)
    t0 = time.perf_counter()
    elbo = model.update_model(stream)
    log(f"stream_fit ({model.backend} backend): first call (compile + run) "
        f"{time.perf_counter() - t0:.1f} s, final-batch ELBO {elbo:.6e}, "
        f"n_seen {model.n_seen}")
    return model, x, means, stream


def compiled_kernel_check(model, n_batches: int, batch: int) -> None:
    """The stream scan that ``update_model`` ran, compiled again ahead of
    time: the E-step must be the compiled Pallas kernels."""
    import jax
    import jax.numpy as jnp

    from repro.core import streaming
    from repro.kernels import clg_stats

    interpret = clg_stats._resolve_interpret(None)
    log(f"kernel policy: interpret={interpret}, backend={model.backend}")
    if interpret or model.backend != "pallas":
        raise AssertionError("the stream path is not on compiled kernels")
    F = model.cp.layout.F
    state = streaming.stream_init(model.prior, model.posterior)
    t0 = time.perf_counter()
    lowered = streaming._stream_fit_scan.lower(
        model.cp, model.prior, state,
        jax.ShapeDtypeStruct((n_batches, batch, F), jnp.float32),
        jax.ShapeDtypeStruct((n_batches, batch, 0), jnp.int32),
        jax.ShapeDtypeStruct((n_batches, batch), jnp.float32),
        sweeps=100, tol=1e-5, drift_threshold=5.0, forget=0.3,
        backend=model.backend, chunk=model.chunk)
    text = lowered.compile().as_text()
    n_calls = text.count("custom_call_target=\"tpu_custom_call\"")
    log(f"stream_fit scan compiled in {time.perf_counter() - t0:.1f} s: "
        f"{n_calls} tpu_custom_call op(s)")
    if n_calls == 0:
        raise AssertionError("no tpu_custom_call in the compiled stream scan")


def reference_phase(model, stream, means, seed: int) -> None:
    """The same stream through the einsum backend at highest precision."""
    import jax

    from repro.pgm_models.static import GaussianMixture

    ref = GaussianMixture(stream.attributes, n_states=len(means), seed=seed,
                          backend="einsum")
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref.update_model(stream)
    log(f"reference fit (einsum, highest precision): "
        f"{time.perf_counter() - t0:.1f} s")
    compare_posteriors(
        posterior_summary(model), posterior_summary(ref), "pallas vs einsum",
        count_tol=1e-4, mean_tol=1e-4,
        why="f32 sums of 2^20 terms per batch in another order")
    check_true_means("fitted vs generator means",
                     posterior_summary(model)[1], means, model.n_seen)


def serve_phase(model, x, seed: int, n_queries: int = 48) -> None:
    """A few dozen queries through the async server in both modes."""
    import jax.numpy as jnp

    from repro.data.stream import Batch
    from repro.data.synthetic import random_discrete_bn
    from repro.infer_exact.brute import brute_posterior
    from repro.serve.queue import AsyncPGMServer

    rows = x[:n_queries]
    F = rows.shape[1]
    with AsyncPGMServer(model, mode="vmp", max_batch=16,
                        default_deadline_ms=60_000) as srv:
        t0 = time.perf_counter()
        tickets = [srv.submit("Z", {f"X{i}": float(r[i]) for i in range(F)})
                   for r in rows]
        got = np.stack([t.result(timeout=600) for t in tickets])
        log(f"vmp serving: {n_queries} queries answered in "
            f"{time.perf_counter() - t0:.2f} s (compiles included)")
    direct = np.asarray(model.posterior_z(
        Batch(jnp.asarray(rows), jnp.zeros((n_queries, 0), jnp.int32),
              jnp.ones(n_queries, jnp.float32))))
    check("vmp server vs posterior_z", float(np.abs(got - direct).max()),
          1e-5, why="same posterior and rows; only the padded batch shape "
                    "differs")

    bn = random_discrete_bn(8, card=3, max_parents=2, seed=seed)
    names = [v.name for v in bn.order]
    rng = np.random.default_rng(seed)
    queries = []
    for _ in range(n_queries):
        obs = rng.choice(len(names) - 1, size=3, replace=False)
        queries.append((names[-1], {names[i]: int(rng.integers(0, 3))
                                    for i in obs}))
    with AsyncPGMServer(bn, mode="exact", max_batch=16,
                        default_deadline_ms=60_000) as srv:
        t0 = time.perf_counter()
        tickets = [srv.submit(t, e) for t, e in queries]
        got = [t.result(timeout=600) for t in tickets]
        log(f"exact serving: {n_queries} queries answered in "
            f"{time.perf_counter() - t0:.2f} s (compiles included)")
    err = max(float(np.abs(np.asarray(g) - np.asarray(brute_posterior(
        bn, bn.dag.variables.by_name(t), e))).max())
        for g, (t, e) in zip(got, queries))
    check("exact server vs brute enumeration", err, 1e-4,
          why="f32 log-space junction tree vs full enumeration of 3^8 "
              "configurations")


def one_chip(seed: int, n_batches: int = 16, batch: int = 1 << 20) -> None:
    import jax

    model, x, means, stream = stream_phase(seed, n_batches, batch)
    compiled_kernel_check(model, n_batches, batch)
    reference_phase(model, stream, means, seed)
    serve_phase(model, x, seed)
    log(f"peak_bytes_in_use: {peak_bytes(jax.devices()[0]) / 1e9:.3f} GB")


# ---------------------------------------------------------------------------
# four chips: d-VMP
# ---------------------------------------------------------------------------


def four_chips(seed: int, n: int = 1 << 24) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data.stream import Batch
    from repro.launch.mesh import make_mesh
    from repro.pgm_models.static import GaussianMixture

    devices = jax.devices()[:4]
    attrs, x, means = gmm_data(n, seed)
    log(f"data: {n} instances x {x.shape[1]} features "
        f"({x.nbytes / 1e6:.1f} MB in all)")
    mesh = make_mesh((4,), ("data",), devices=devices)
    shard = NamedSharding(mesh, P("data"))
    batch = jax.device_put(
        Batch(x, np.zeros((n, 0), np.int32), np.ones(n, np.float32)), shard)

    dist = GaussianMixture(attrs, n_states=len(means), seed=seed)
    t0 = time.perf_counter()
    elbo = dist.update_model(batch, mesh=mesh)
    log(f"d-VMP over 4 chips ({dist.backend} backend): compile + fit "
        f"{time.perf_counter() - t0:.1f} s, ELBO {elbo:.6e}")
    shards = sorted((s.device.id, s.data.shape[0])
                    for s in batch.xc.addressable_shards)
    log(f"  data shards (device, instances): {shards}")
    if shards != sorted((d.id, n // 4) for d in devices):
        raise AssertionError("the data is not split over the four chips")
    peaks = [peak_bytes(d) for d in devices]
    for d, p in zip(devices, peaks):
        log(f"  device {d.id} peak_bytes_in_use: {p / 1e9:.3f} GB")
    # a chip that gathered the whole set would peak >= 3/4 of it above
    # the others
    if max(peaks) - min(peaks) >= 0.75 * x.nbytes:
        raise AssertionError("one chip held the whole data set")

    # the comparison: one device, the same data
    single = GaussianMixture(attrs, n_states=len(means), seed=seed)
    local = Batch(jax.device_put(x, devices[0]),
                  jnp.zeros((n, 0), jnp.int32, device=devices[0]),
                  jnp.ones(n, jnp.float32, device=devices[0]))
    t0 = time.perf_counter()
    single.update_model(local)
    log(f"single-device fit: compile + fit {time.perf_counter() - t0:.1f} s")
    compare_posteriors(
        posterior_summary(dist), posterior_summary(single),
        "d-VMP vs single device", count_tol=3e-5, mean_tol=1e-4,
        why="f32 sums in another order: four shard sums added by psum vs "
            "one kernel pass over all 2^24 instances")
    check_true_means("d-VMP vs generator means",
                     posterior_summary(dist)[1], means, n)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only d-VMP across four chips and the "
                         "single-device fit it is compared with")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    import jax

    devices = jax.devices()
    d0 = devices[0]
    log(f"platform {d0.platform}, device_kind {d0.device_kind!r}, "
        f"{len(devices)} device(s)")
    if d0.platform != "tpu":
        log("no TPU: this smoke runs only on the chip")
        return 2
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices")
        return 2
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
