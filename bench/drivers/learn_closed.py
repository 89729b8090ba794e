"""Closed-loop streaming learner: back-to-back ``update_model`` calls.

Traffic keys: ``pool_batches`` batches of ``batch`` instances are made on
the device and brought to the host once (set-up), from the seed, or from
``pool_seed`` when the mix gives one, in which case the run's seed draws
the order in which the pool is replayed: every seed then learns the same
set of batches, so the number of sweeps (which depends on the rows) does
not move with the seed.  Call ``j`` feeds the ``call_batches`` pool
batches from ``j * call_batches`` on, round the pool, either as one array
(``feed: "array"``, one batch per call) or as a ``DataStream`` of that many
chunks (``feed: "stream"``, which routes to the ``stream_fit`` scan and
its drift test); ``sweeps`` and ``tol`` go to ``update_model``.

Set-up builds the model and drives it through the first
``checked_calls`` calls of the window's own loop; the window continues the
same loop on the same model.  ``learn_inst_per_s`` is all instances of all
calls over the window; ``update_ms_p95`` the 95th percentile of every
call's time, from handing over host data to the posterior being ready.

After the window the reference follows the checked calls on the same data
and its readings decide ``correct`` (``bench.compare.learn_readings``).
"""

from __future__ import annotations

import time

import numpy as np

from bench import compare, gen
from bench.run import Outcome, span


def model_class(cfg):
    from repro.pgm_models import static

    return getattr(static, cfg["model"])


def attributes(cfg):
    from repro.data.stream import Attribute, FINITE, REAL

    return ([Attribute(f"X{i}", REAL) for i in range(cfg["continuous"])]
            + [Attribute(f"D{i}", FINITE, c)
               for i, c in enumerate(cfg["discrete_cards"])])


def model_seed(cell) -> int:
    """The model's own seed (its symmetry-breaking start), from the run's."""
    return int(cell.seed) % (2 ** 31)


class Feed:
    """The host pool and the data of each call."""

    def __init__(self, cfg, traffic, seed):
        t = traffic
        self.cfg, self.traffic = cfg, t
        self.n_call = t["call_batches"]
        P = t["pool_batches"]
        self.xc, self.xd = gen.host_batches(cfg, t, t.get("pool_seed", seed),
                                            P, t["batch"])
        self.order = (np.random.default_rng(seed).permutation(P)
                      if "pool_seed" in t else np.arange(P))
        self.attrs = attributes(cfg)

    def chunks(self, j: int):
        P = len(self.order)
        idx = [self.order[(j * self.n_call + i) % P]
               for i in range(self.n_call)]
        return [(self.xc[k], self.xd[k]) for k in idx]

    def instances(self) -> int:
        return self.n_call * self.traffic["batch"]

    def data(self, j: int):
        """Call ``j``'s argument to ``update_model``."""
        from repro.data.stream import Batch, DataStream

        chunks = self.chunks(j)
        if self.traffic["feed"] == "stream":
            return DataStream(self.attrs, lambda: iter(chunks),
                              n_instances=self.instances())
        (xc, xd), = chunks
        if xd.shape[1] == 0:
            return xc
        return Batch(xc, xd, np.ones(xc.shape[0], np.float32))


def natural_of(post) -> dict:
    """The program's posterior in the reference's natural coordinates."""
    m = np.asarray(post.reg.m, np.float64)[..., 0]
    kk = np.asarray(post.reg.K, np.float64)[..., 0, 0]
    b = np.asarray(post.reg.b, np.float64)
    km = kk * m
    return dict(mix=np.asarray(post.mix.alpha, np.float64), kk=kk, km=km,
                a=np.asarray(post.reg.a, np.float64), bq=b + 0.5 * m * km,
                disc=np.asarray(post.disc.alpha, np.float64))


def call(model, feed, j, traffic) -> float:
    """One call of the loop: host data in, posterior ready out."""
    import jax

    with span("update_model"):
        e = model.update_model(feed.data(j), sweeps=traffic["sweeps"],
                               tol=traffic["tol"])
        jax.block_until_ready(model.posterior)
    return e


def checked_steps(model, feed, traffic) -> dict:
    """Drive a fresh model through the checked calls (set-up)."""
    rec = {"prior": natural_of(model._chained_prior),
           "init": natural_of(model.posterior), "posts": [], "elbos": []}
    for j in range(traffic["checked_calls"]):
        rec["elbos"].append(float(call(model, feed, j, traffic)))
        rec["posts"].append(natural_of(model.posterior))
    return rec


def reference_steps(cell, feed, ref, dtype, fault=None) -> dict:
    """The reference through the checked calls: ``fault`` may plant one of
    the faults the output check must catch (``"half_batch"``)."""
    import jax.numpy as jnp

    cfg, t = cell.cfg, cell.traffic
    base = ref.prior(cfg, dtype)
    post = ref.initial(base, model_seed(cell), dtype)

    def nat(p):
        return {k: np.asarray(v, np.float64)
                for k, v in ref.natural(p).items()}

    rec = {"prior": nat(base), "init": nat(post), "posts": [], "elbos": [],
           "sweeps": []}
    chained = base
    for j in range(t["checked_calls"]):
        chunks = [(jnp.asarray(xc), jnp.asarray(xd))
                  for xc, xd in feed.chunks(j)]
        if fault == "half_batch":
            chunks = [(xc[: len(xc) // 2], xd[: len(xd) // 2])
                      for xc, xd in chunks]
        if t["feed"] == "stream":
            post, e, sw, fired = ref.stream_call(base, chained, post, chunks,
                                                 t["sweeps"], t["tol"])
            rec["sweeps"].append(sw)
            rec.setdefault("drift", []).append(fired)
        else:
            (xc, xd), = chunks
            post, e, sw = ref.fit(chained, post, xc, xd, t["sweeps"],
                                  t["tol"])
            rec["sweeps"].append(sw)
        chained = post
        rec["elbos"].append(e)
        rec["posts"].append(nat(post))
    return rec


def run(cell, window) -> Outcome:
    import jax

    t = cell.traffic
    feed = Feed(cell.cfg, t, cell.seed)
    model = model_class(cell.cfg)(feed.attrs, n_states=cell.cfg["latent_card"],
                                  seed=model_seed(cell))
    prog = checked_steps(model, feed, t)
    j = t["checked_calls"]
    times = []
    with window:
        t0 = time.monotonic()
        end = t0 + cell.seconds
        while time.monotonic() < end:
            c0 = time.monotonic()
            call(model, feed, j, t)
            times.append(time.monotonic() - c0)
            j += 1
        elapsed = time.monotonic() - t0
    calls = len(times)
    del model
    cell.log(f"window: {calls} calls of {feed.instances()} instances in "
             f"{elapsed:.3f} s; call ms p50 {np.percentile(times, 50) * 1e3:.3f}"
             f" max {max(times) * 1e3:.3f}")
    cell.log("sweeps per call: not exposed by update_model")

    def check():
        ref = cell.reference()
        with jax.default_device(cell.devices[0]):
            rec = reference_steps(cell, feed, ref, jax.numpy.float32)
        cell.log(f"reference sweeps per checked call: {rec['sweeps']}")
        if "drift" in rec:
            cell.log(f"reference drift firings per checked call: "
                     f"{rec['drift']}")
        return compare.learn_readings(prog, rec)

    return Outcome(
        e2e={"learn_inst_per_s": calls * feed.instances() / elapsed,
             "update_ms_p95": float(np.percentile(times, 95)) * 1e3},
        attempted=calls, failed=0, check=check,
        counters={"calls": calls, "instances_per_call": feed.instances(),
                  "batch": t["batch"]})
