"""Serving tier: request lifecycle, continuous batching, the plan/run API,
async deadline-aware micro-batching, hot model swap, replica sharding."""

import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import synthetic as syn
from repro.nn import transformer as T
from repro.serve.engine import DecodeEngine, PGMQueryEngine, Request
from repro.serve.plan import CompiledPlan, PlanCache, PlanKey
from repro.serve.queue import AsyncPGMServer


def _engine(arch="granite-3-2b", batch=2, capacity=64):
    cfg = get_config(arch).reduced()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    return DecodeEngine(params, cfg, batch, capacity), cfg


def test_engine_drains_all_requests():
    eng, cfg = _engine()
    for rid in range(5):
        eng.submit(Request(rid=rid, prompt=[1, 2, 3], max_new=6))
    reqs = list(eng.queue)
    eng.run()
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 6 for r in reqs)
    assert all(0 <= t < cfg.vocab for r in reqs for t in r.out)


def test_engine_continuous_batching_reuses_slots():
    eng, _ = _engine(batch=2)
    reqs = [Request(rid=i, prompt=[i + 1], max_new=3) for i in range(6)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)   # 6 requests through 2 slots


def test_greedy_engine_matches_direct_decode():
    """A single request in slot 0 must reproduce plain greedy decoding."""
    cfg = get_config("granite-3-2b").reduced()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    prompt, n_new = [5, 9, 2], 5
    # direct
    state = T.init_decode_state(params, cfg, 1, 64)
    toks = []
    cur = jnp.asarray([[prompt[0]]], jnp.int32)
    pending = prompt[1:]
    for _ in range(len(prompt) + n_new - 1):
        logits, state = T.decode_step(params, state, cur, cfg)
        if pending:
            cur = jnp.asarray([[pending.pop(0)]], jnp.int32)
        else:
            nxt = int(logits[0, 0].argmax())
            toks.append(nxt)
            cur = jnp.asarray([[nxt]], jnp.int32)
            if len(toks) == n_new:
                break
    # engine (batch=1)
    eng = DecodeEngine(params, cfg, 1, 64)
    req = Request(rid=0, prompt=list(prompt), max_new=n_new)
    eng.submit(req)
    eng.run()
    assert req.out == toks, (req.out, toks)


# ---------------------------------------------------------------------------
# plan API (repro.serve.plan)
# ---------------------------------------------------------------------------


def _key(i, version=0, mode="jt-discrete"):
    return PlanKey(version, mode, (f"D{i}",), (4,), ("float32",))


def test_plan_cache_hit_miss_counters_and_compile_timing():
    cache = PlanCache(max_plans=8)
    assert cache.get(_key(0)) is None           # miss, no build
    plan = cache.get(_key(0), lambda: (lambda x: x + 1))
    assert isinstance(plan, CompiledPlan)
    assert plan.compile_us > 0.0
    assert plan.run(1) == 2 and plan.runs == 1
    again = cache.get(_key(0), lambda: (lambda x: x + 100))
    assert again is plan                        # hit: build never called
    st = cache.stats()
    assert st == {"hits": 1, "misses": 2, "evictions": 0, "size": 1,
                  "max_plans": 8, "hit_rate": 1 / 3, "retries": 0}
    # peek touches neither counters nor LRU order
    assert cache.peek(_key(0)) is plan
    assert cache.stats()["hits"] == 1


def test_plan_cache_lru_eviction():
    cache = PlanCache(max_plans=3)
    for i in range(3):
        cache.get(_key(i), lambda: (lambda x: x))
    cache.get(_key(0))                          # refresh 0 -> LRU order 1,2,0
    cache.get(_key(3), lambda: (lambda x: x))   # evicts 1
    assert cache.stats()["evictions"] == 1
    assert _key(1) not in cache
    assert all(k in cache for k in (_key(0), _key(2), _key(3)))


def test_plan_cache_invalidate_by_network_version():
    cache = PlanCache()
    for v in (0, 0, 1):
        for i in range(2):
            cache.get(_key(i, version=v), lambda: (lambda x: x))
    assert len(cache) == 4
    assert cache.invalidate(0) == 2             # the hot-swap drain path
    assert all(k.network_version == 1 for k in cache.keys())
    assert cache.invalidate() == 2              # drop-all flavor
    assert len(cache) == 0


# ---------------------------------------------------------------------------
# PGMQueryEngine on the plan cache
# ---------------------------------------------------------------------------


def _discrete_bn(seed=0):
    return syn.random_discrete_bn(5, card=2, max_parents=2, seed=seed)


def test_flush_returns_submission_order_for_interleaved_schemas():
    """Regression: flush() used to return bucket order — results must come
    back keyed by request id (submission order) under schema interleave."""
    bn = _discrete_bn()
    names = [v.name for v in bn.order]
    eng = PGMQueryEngine(bn, mode="exact")
    schemas = [{names[0]: 1.0}, {names[1]: 0.0, names[2]: 1.0}]
    qs = [eng.submit(names[-1], schemas[i % 2]) for i in range(7)]
    done = eng.flush()
    assert [q.qid for q in done] == [q.qid for q in qs]
    assert all(q.done for q in done)
    # and per-request answers match a bucket-homogeneous run
    ref = PGMQueryEngine(bn, mode="exact")
    for i in (0, 1):
        r = ref.submit(names[-1], schemas[i])
        ref.flush()
        for q in done[i::2]:
            assert np.allclose(q.result, r.result, atol=1e-6)


def test_jt_plans_live_in_shared_plan_cache():
    bn = _discrete_bn()
    names = [v.name for v in bn.order]
    cache = PlanCache()
    eng = PGMQueryEngine(bn, mode="exact", plan_cache=cache)
    eng.submit(names[-1], {names[0]: 1.0})
    eng.flush()
    keys = cache.keys()
    assert len(keys) == 1 and keys[0].mode == "jt-discrete"
    assert keys[0].network_version == 0
    # same schema + batch again: a cache hit, no new plan
    eng.submit(names[-1], {names[0]: 0.0})
    eng.flush()
    assert len(cache) == 1 and cache.stats()["hits"] >= 1


def test_set_model_bumps_version_and_old_plans_stop_hitting():
    bn, bn2 = _discrete_bn(0), _discrete_bn(9)
    names = [v.name for v in bn.order]
    eng = PGMQueryEngine(bn, mode="exact")
    q0 = eng.submit(names[-1], {names[0]: 1.0})
    eng.flush()
    eng.set_model(bn2)
    assert eng.network_version == 1
    q1 = eng.submit(names[-1], {names[0]: 1.0})
    eng.flush()
    assert not np.allclose(q0.result, q1.result)    # new CPDs actually serve
    versions = {k.network_version for k in eng.plans.keys()}
    assert versions == {0, 1}                       # old plan aged, not reused


def test_exact_pad_pow2_matches_unpadded():
    bn = _discrete_bn()
    names = [v.name for v in bn.order]
    ev = [{names[0]: float(i % 2), names[1]: float((i // 2) % 2)}
          for i in range(5)]
    plain = PGMQueryEngine(bn, mode="exact")
    padded = PGMQueryEngine(bn, mode="exact", pad_pow2=True)
    for e in ev:
        plain.submit(names[-1], e)
        padded.submit(names[-1], e)
    a, b = plain.flush(), padded.flush()
    for qa, qb in zip(a, b):
        assert np.allclose(qa.result, qb.result, atol=1e-6)
        assert np.isclose(qa.log_evidence, qb.log_evidence, atol=1e-6)
    # the padded engine compiled for the pow2 capacity
    assert {k.batch_shape[0] for k in padded.plans.keys()} == {8}


def test_deprecated_cache_shims_warn_and_reflect_plans():
    bn = _discrete_bn()
    names = [v.name for v in bn.order]
    eng = PGMQueryEngine(bn, mode="exact")
    eng.submit(names[-1], {names[0]: 1.0})
    eng.flush()
    with pytest.warns(DeprecationWarning):
        compiled = eng._jt._compiled
    assert len(compiled) == 1
    ((schema, batch, dtypes),) = compiled.keys()
    assert schema == (names[0],) and batch == 1
    with pytest.warns(DeprecationWarning):
        assert eng._vmp_caps == set()
    with pytest.warns(DeprecationWarning):
        assert eng._temporal_keys == set()


# ---------------------------------------------------------------------------
# AsyncPGMServer: micro-batching, deadlines, hot swap
# ---------------------------------------------------------------------------


def _direct_answers(bn, queries, **engine_kw):
    eng = PGMQueryEngine(bn, mode="exact", **engine_kw)
    qs = [eng.submit(t, e) for t, e in queries]
    eng.flush()
    return [q.result for q in qs]


def test_async_size_trigger_matches_direct_engine():
    """A size-triggered micro-batch must be bit-identical to the direct
    engine on the same queries (same bucket, same pow2 padding)."""
    bn = _discrete_bn()
    names = [v.name for v in bn.order]
    queries = [(names[-1], {names[0]: float(i % 2)}) for i in range(4)]
    with AsyncPGMServer(bn, mode="exact", max_batch=4,
                        max_delay_ms=10_000, default_deadline_ms=60_000,
                        deadline_margin_ms=0.0) as srv:
        tickets = [srv.submit(t, e) for t, e in queries]
        results = [t.result(timeout=120) for t in tickets]
        assert all(t.trigger == "size" for t in tickets)
    direct = _direct_answers(bn, queries, pad_pow2=True)
    for r, d in zip(results, direct):
        assert np.array_equal(r, d)


def test_async_timeout_trigger_matches_direct_engine():
    bn = _discrete_bn()
    names = [v.name for v in bn.order]
    queries = [(names[-1], {names[1]: 1.0}), (names[-1], {names[1]: 0.0})]
    with AsyncPGMServer(bn, mode="exact", max_batch=64, max_delay_ms=50,
                        default_deadline_ms=60_000) as srv:
        tickets = [srv.submit(t, e) for t, e in queries]
        results = [t.result(timeout=120) for t in tickets]
        assert all(t.trigger == "timeout" for t in tickets)
    direct = _direct_answers(bn, queries, pad_pow2=True)
    for r, d in zip(results, direct):
        assert np.array_equal(r, d)


def test_deadline_drives_flush_order_across_mixed_schemas():
    bn = _discrete_bn()
    names = [v.name for v in bn.order]
    slow = (names[-1], {names[0]: 1.0})
    fast = (names[-1], {names[1]: 1.0, names[2]: 0.0})
    with AsyncPGMServer(bn, mode="exact", max_batch=64,
                        max_delay_ms=10_000, default_deadline_ms=60_000,
                        deadline_margin_ms=100.0) as srv:
        # warm both plans so flush order is not compile-order noise
        for t, e in (slow, fast):
            srv.submit(t, e, deadline_ms=1.0).result(timeout=120)
        t_slow = srv.submit(*slow, deadline_ms=2_000)   # submitted FIRST
        t_fast = srv.submit(*fast, deadline_ms=500)     # tighter deadline
        t_fast.result(timeout=120)
        t_slow.result(timeout=120)
        assert t_fast.trigger == "deadline"
        assert t_fast.done_s < t_slow.done_s    # deadline order, not FIFO
    assert t_fast.deadline_miss is False        # margin held: flushed early


def test_hot_swap_mid_stream_drops_nothing_and_changes_answers():
    bn, bn2 = _discrete_bn(0), _discrete_bn(9)
    names = [v.name for v in bn.order]
    query = (names[-1], {names[0]: 1.0})
    with AsyncPGMServer(bn, mode="exact", max_batch=8, max_delay_ms=5,
                        default_deadline_ms=60_000) as srv:
        srv.submit(*query).result(timeout=120)      # warm v0
        tickets, stop = [], threading.Event()

        def pump():
            while not stop.is_set():
                tickets.append(srv.submit(*query))
                time.sleep(0.002)

        th = threading.Thread(target=pump)
        th.start()
        try:
            time.sleep(0.05)
            info = srv.swap_model(bn2)
            time.sleep(0.05)
        finally:
            stop.set()
            th.join()
        results = [t.result(timeout=120) for t in tickets]
        assert srv.stats()["pending"] == 0          # zero dropped requests
        assert info["new_version"] == 1 and info["warmed_plans"] >= 1
    assert all(t.error is None for t in tickets)
    old = _direct_answers(bn, [query], pad_pow2=True)[0]
    new = _direct_answers(bn2, [query], pad_pow2=True)[0]
    assert not np.allclose(old, new)                # swap is observable
    for r in results:                               # every answer is one of
        assert np.allclose(r, old) or np.allclose(r, new)
    assert any(np.allclose(r, new) for r in results)
    # old-version plans were invalidated by the drain
    assert all(k.network_version == 1 for k in srv.plans.keys())


def test_async_vmp_replicas_match_single_worker():
    stream, _, _ = syn.gmm_stream(400, 3, 4, seed=1)
    from repro.pgm_models import GaussianMixture

    m = GaussianMixture(stream.attributes, n_states=3)
    m.update_model(stream)
    xs = np.asarray(stream.collect().xc)
    queries = [("Z", {f"X{i}": float(xs[j, i]) for i in range(4)})
               for j in range(12)]

    def run(replicas):
        with AsyncPGMServer(m, mode="vmp", max_batch=4, max_delay_ms=20,
                            default_deadline_ms=60_000,
                            replicas=replicas) as srv:
            tickets = [srv.submit(t, e) for t, e in queries]
            return [t.result(timeout=120) for t in tickets]

    one, three = run(1), run(3)
    for a, b in zip(one, three):
        assert np.allclose(a, b, atol=1e-6)


def test_mesh_replica_parity_with_single_device():
    """dvmp_posterior_z row-parity with single-device posterior_z, on a
    forced multi-device host (subprocess, like tests/test_distributed)."""
    from test_distributed import run_with_devices

    out = run_with_devices("""
        import numpy as np
        from repro.data import synthetic as syn
        from repro.pgm_models import GaussianMixture
        from repro.serve.engine import PGMQueryEngine
        from repro.launch.mesh import make_mesh

        stream, _, _ = syn.gmm_stream(256, 3, 4, seed=1)
        m = GaussianMixture(stream.attributes, n_states=3)
        m.update_model(stream)
        xs = np.asarray(stream.collect().xc)
        mesh = make_mesh((4,), ("data",))
        single = PGMQueryEngine(m, mode="vmp")
        sharded = PGMQueryEngine(m, mode="vmp", mesh=mesh)
        for eng in (single, sharded):
            for j in range(10):
                eng.submit("Z", {f"X{i}": float(xs[j, i]) for i in range(4)})
        a, b = single.flush(), sharded.flush()
        for qa, qb in zip(a, b):
            assert np.allclose(qa.result, qb.result, atol=1e-5), (qa.qid)
        assert any(k.mode == "vmp" for k in sharded.plans.keys())
        print("MESH_SERVE_OK")
    """, n=4)
    assert "MESH_SERVE_OK" in out
