"""Serving driver: ``python -m repro.launch.serve``.

Two paths:

* ``--arch <id>`` — the original LM demo: random-init a reduced model and
  drain a batch of synthetic requests through the continuous-batching
  DecodeEngine.

* default (no ``--arch``) — drive the async PGM serving tier
  (:class:`repro.serve.queue.AsyncPGMServer`) under Poisson offered load:
  a synthetic discrete network (or a vmp-served GaussianMixture with
  ``--mode vmp``), exponential inter-arrival times at ``--load`` queries/s,
  per-request deadlines from ``--deadline-ms``, optional mid-run hot model
  swap (``--swap``).  Progress and the final latency summary go through
  ``repro.obs`` (structured ``log`` events + the serving tier's own
  ``serve_*`` telemetry) instead of prints.
"""

from __future__ import annotations

import argparse
import time

from repro.launch.cache import use_compile_cache


def _serve_lm(args) -> int:
    import jax
    import numpy as np

    from repro import obs
    from repro.configs import get_config
    from repro.nn import transformer as T
    from repro.serve.engine import DecodeEngine, Request
    from repro.train import checkpoint as ck

    cfg = get_config(args.arch).reduced()
    key = jax.random.PRNGKey(args.seed)
    params = T.init_model(key, cfg)
    if args.ckpt:
        params = ck.load(args.ckpt, params)

    engine = DecodeEngine(params, cfg, args.batch, args.capacity)
    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, rng.integers(4, 12)).tolist()
        engine.submit(Request(rid=rid, prompt=prompt, max_new=args.max_new))

    t0 = time.time()
    steps = 0
    while True:
        active = engine.step()
        steps += 1
        if active == 0 and not engine.queue:
            break
        if steps > 100_000:
            raise RuntimeError("serve loop did not drain")
    dt = time.time() - t0
    done = args.requests
    toks = done * args.max_new
    obs.log(f"[serve] {done} requests, {toks} tokens in {dt:.1f}s "
            f"({toks/dt:,.0f} tok/s, batch={args.batch})",
            component="serve", requests=done, tokens=toks, seconds=dt,
            tok_s=toks / dt, batch=args.batch)
    return 0


def _serve_pgm(args) -> int:
    import numpy as np

    from repro import obs
    from repro.data import synthetic as syn
    from repro.serve.queue import AsyncPGMServer

    rng = np.random.default_rng(args.seed)
    if args.mode == "vmp":
        from repro.pgm_models import GaussianMixture

        s, _, _ = syn.gmm_stream(512, 3, 4, seed=args.seed)
        model = GaussianMixture(s.attributes, n_states=3)
        model.update_model(s)
        xs = np.asarray(s.collect().xc)

        def make_query():
            row = xs[rng.integers(len(xs))]
            return "Z", {f"X{i}": float(row[i]) for i in range(xs.shape[1])}
    else:
        bn = syn.random_discrete_bn(args.vars, card=2, max_parents=2,
                                    seed=args.seed)
        names = [v.name for v in bn.order]
        model = bn
        # a few evidence schemas so the bucket/coalescing path is exercised
        schemas = [names[:1], names[1:3], names[:2]]

        def make_query():
            sc = schemas[rng.integers(len(schemas))]
            return names[-1], {n: float(rng.integers(2)) for n in sc}

    server = AsyncPGMServer(model, mode=args.mode, max_batch=args.max_batch,
                            max_delay_ms=args.max_delay_ms,
                            default_deadline_ms=args.deadline_ms,
                            replicas=args.replicas)
    obs.log(f"[serve] async PGM tier up: mode={args.mode} "
            f"load={args.load}/s deadline={args.deadline_ms}ms "
            f"replicas={args.replicas}", component="serve")

    tickets = []
    swapped = False
    t0 = time.monotonic()
    end = t0 + args.duration
    while time.monotonic() < end:
        target, evidence = make_query()
        tickets.append(server.submit(target, evidence,
                                     deadline_ms=args.deadline_ms))
        if args.swap and not swapped and time.monotonic() - t0 > args.duration / 2:
            if args.mode == "exact":
                bn2 = syn.random_discrete_bn(args.vars, card=2, max_parents=2,
                                             seed=args.seed + 1)
                info = server.swap_model(bn2)
            else:
                model.update_model(xs[:256])
                info = server.swap_model(model)
            obs.log(f"[serve] hot swap v{info['old_version']}->"
                    f"v{info['new_version']} warmed={info['warmed_plans']} "
                    f"drained={info['drained']}", component="serve")
            swapped = True
        # Poisson arrivals at the offered load
        time.sleep(rng.exponential(1.0 / args.load))
    server.stop()

    for t in tickets:
        t.result(timeout=60)        # all served — stop() drained the queue
    lat_ms = np.array([(t.done_s - t.submitted_s) * 1e3 for t in tickets])
    st = server.stats()
    dt = time.monotonic() - t0
    n = len(tickets)
    obs.log(f"[serve] {n} queries in {dt:.1f}s "
            f"({n/dt:,.0f} q/s achieved vs {args.load}/s offered), "
            f"p50 {np.percentile(lat_ms, 50):.2f}ms "
            f"p99 {np.percentile(lat_ms, 99):.2f}ms, "
            f"deadline misses {st['deadline_misses']}/{n}, "
            f"flushes {st['flushes']}, "
            f"plan hit-rate {st['plans']['hit_rate']:.2f}",
            component="serve", queries=n, seconds=dt, qps=n / dt,
            offered=args.load, p50_ms=float(np.percentile(lat_ms, 50)),
            p99_ms=float(np.percentile(lat_ms, 99)),
            deadline_misses=st["deadline_misses"],
            flushes=st["flushes"], plan_stats=st["plans"])
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None,
                    help="LM decode demo arch id (omit for the PGM tier)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--capacity", type=int, default=256)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    # async PGM tier knobs
    ap.add_argument("--mode", default="exact", choices=["exact", "vmp"])
    ap.add_argument("--vars", type=int, default=6,
                    help="exact mode: synthetic network size")
    ap.add_argument("--load", type=float, default=200.0,
                    help="offered load, queries/s (Poisson)")
    ap.add_argument("--duration", type=float, default=3.0,
                    help="offered-load window, seconds")
    ap.add_argument("--deadline-ms", type=float, default=50.0,
                    help="per-request deadline")
    ap.add_argument("--max-delay-ms", type=float, default=5.0,
                    help="micro-batch coalescing window")
    ap.add_argument("--max-batch", type=int, default=32,
                    help="micro-batch size trigger")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--swap", action="store_true",
                    help="hot-swap the model mid-run")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.arch is not None:
        return _serve_lm(args)
    return _serve_pgm(args)


if __name__ == "__main__":
    raise SystemExit(main())
