"""Open-loop query clients: posterior queries on a fixed Poisson schedule.

Traffic keys: a pool of ``pool`` instances is made on the device from the
seed; set-up fits the model on it with one ``update_model`` call (``sweeps``,
``tol``) and starts ``AsyncPGMServer(mode="vmp")`` with its defaults.  The
window sends ``round(rate_qps * seconds)`` queries ``q(Z | x)`` on pool rows
drawn from the seed, at arrival times drawn from the seed as Poisson gaps
scaled to span the window exactly, so every seed offers the same load.
Each query is sent when it is due, whether or not earlier ones are
answered; the generator sleeps between sends (a spinning sender would hold
the interpreter lock from the server's threads) and its lateness is
reported.

``query_ms_p95`` is the 95th percentile over every scheduled query of the
time from its scheduled send to its answer; a query that fails or is still
unanswered a minute after the window closes counts as later than any
answer.  ``query_ok_per_s`` counts the answers that match the reference
and came within ``deadline_ms`` of their scheduled send, over the window.

After the window the reference fits its own posterior on the pool from
the same starting point and answers every query; ``answer_gap`` is the
largest gap of any log-probability (``bench.compare.log_gaps``).
"""

from __future__ import annotations

import time

import numpy as np

from bench import compare, gen
from bench.drivers.learn_closed import attributes, model_class, model_seed
from bench.run import Outcome, span

LATE_S = 60.0      # how long past the window an answer is waited for


def schedule(seed: int, rate: float, seconds: float, pool: int):
    """Send offsets (s) and pool rows of every query of the window."""
    rng = np.random.default_rng(seed)
    n = max(1, int(round(rate * seconds)))
    gaps = rng.exponential(1.0, n + 1)
    due = np.cumsum(gaps)[:-1] / gaps.sum() * seconds
    return due, rng.integers(0, pool, n)


def send(server, due, rows, evidence):
    """Submit each query at its due time; returns the start of the
    schedule, the tickets and each send's lateness (s)."""
    tickets = [None] * len(due)
    late = np.zeros(len(due))
    t0 = time.monotonic() + 0.01
    for i, (d, row) in enumerate(zip(due, rows)):
        at = t0 + d
        now = time.monotonic()
        if at > now:
            time.sleep(at - now)
            now = time.monotonic()
        late[i] = now - at
        tickets[i] = server.submit("Z", evidence[row])
    return t0, tickets, late


def run(cell, window) -> Outcome:
    import jax
    from repro.data.stream import Batch
    from repro.serve.engine import PGMQueryEngine
    from repro.serve.queue import AsyncPGMServer

    cfg, t = cell.cfg, cell.traffic
    xc, xd = gen.host_batches(cfg, t, cell.seed, 1, t["pool"])
    xc, xd = xc[0], xd[0]
    model = model_class(cfg)(attributes(cfg), n_states=cfg["latent_card"],
                             seed=model_seed(cell))
    model.update_model(xc if xd.shape[1] == 0 else Batch(
        xc, xd, np.ones(len(xc), np.float32)), sweeps=t["sweeps"],
        tol=t["tol"])
    due, rows = schedule(cell.seed, t["rate_qps"], cell.seconds, len(xc))
    F = xc.shape[1]
    used = np.unique(rows)
    evidence = {int(r): {f"X{i}": float(xc[r, i]) for i in range(F)}
                for r in used}
    server = AsyncPGMServer(model, mode="vmp")
    # every bucket size the window can flush, through the path it drives
    eng = PGMQueryEngine(model, mode="vmp", plan_cache=server.plans,
                         pad_pow2=True)
    for cap in (1 << k for k in range(t["warm_max_pow2"] + 1)):
        for r in np.resize(rows, cap):
            eng.submit("Z", evidence[int(r)])
        eng.flush()
    warm = [server.submit("Z", evidence[int(r)]) for r in rows[:256]]
    for w in warm:
        w.result(timeout=LATE_S)
    before = server.stats()
    with window:
        with span("send"):
            t0, tickets, late = send(server, due, rows, evidence)
        end = t0 + cell.seconds
        with span("drain"):
            for tk in tickets:
                try:
                    tk.result(timeout=max(0.0, end + LATE_S - time.monotonic()))
                except Exception:       # failed or late: counted below
                    pass
        after = server.stats()
    server.stop()
    answers = np.full((len(due), cfg["latent_card"]), np.nan)
    done_s = np.full(len(due), np.inf)
    failed = 0
    for i, tk in enumerate(tickets):
        if tk.done() and tk.error is None:
            answers[i] = tk.query.result
            done_s[i] = tk.done_s
        else:
            failed += 1
    del model, server, tickets
    lat = np.minimum(done_s - (t0 + due), cell.seconds + LATE_S)
    flushes = sum(after["flushes"].values()) - sum(before["flushes"].values())
    answered = len(due) - failed
    cell.log(f"generator lateness (ms): p50 {np.percentile(late, 50) * 1e3:.3f}"
             f" p95 {np.percentile(late, 95) * 1e3:.3f} max "
             f"{late.max() * 1e3:.3f}")
    cell.log(f"queries: {len(due)} scheduled, {answered} answered, {failed} "
             f"failed or unanswered; flushes {flushes}; latency ms p50 "
             f"{np.percentile(lat, 50) * 1e3:.3f} p95 "
             f"{np.percentile(lat, 95) * 1e3:.3f}")
    e2e = {"query_ms_p95": float(np.percentile(lat, 95)) * 1e3}

    def check():
        ref = cell.reference()
        import jax.numpy as jnp

        with jax.default_device(cell.devices[0]):
            ref_ans = np.asarray(reference_answers(
                cell, ref, jnp.float32, xc, xd, rows))
        good = np.isfinite(answers).all(1)
        gaps = np.full(len(answers), -np.log(compare.FLOOR))
        gaps[good] = compare.log_gaps(answers[good], ref_ans[good])
        ok = good & (gaps <= cell.limits["answer_log_gap"]) & (
            lat <= t["deadline_ms"] / 1e3)
        e2e["query_ok_per_s"] = float(ok.sum()) / cell.seconds
        # no answer reads the widest gap the floor allows
        return {"answer_log_gap": float(gaps.max())}

    return Outcome(e2e=e2e, attempted=len(due), failed=failed, check=check,
                   counters={"answered": answered, "flushes": flushes})


def reference_answers(cell, ref, dtype, xc, xd, rows):
    """The reference's own fit on the pool, then ``q(Z | x)`` per query."""
    import jax.numpy as jnp

    t = cell.traffic
    base = ref.prior(cell.cfg, dtype)
    post = ref.initial(base, model_seed(cell), dtype)
    post, _, _ = ref.fit(base, post, jnp.asarray(xc), jnp.asarray(xd),
                         t["sweeps"], t["tol"])
    return ref.qz(post, jnp.asarray(xc[rows]), jnp.asarray(xd[rows]))
