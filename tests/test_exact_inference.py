"""infer_exact: junction tree vs brute force, HMM oracle, CLG conditioning."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dag import (BayesianNetwork, CLGCPD, DAG, MultinomialCPD,
                            Variables)
from repro.core.factored_frontier import hmm_forward
from repro.infer_exact import (JunctionTreeEngine, brute_posterior,
                               compile_junction_tree)
from repro.infer_exact.graph import verify_running_intersection


def random_discrete_bn(seed: int, n: int = 6, p_edge: float = 0.45):
    rng = np.random.RandomState(seed)
    vs = Variables()
    cards = rng.randint(2, 4, n)
    xs = [vs.new_multinomial(f"V{i}", int(cards[i])) for i in range(n)]
    dag = DAG(vs)
    cpds = {}
    for i, v in enumerate(xs):
        pa = [xs[j] for j in range(i) if rng.rand() < p_edge]
        for p in pa:
            dag.add_parent(v, p)
        shape = tuple(p.card for p in pa) + (v.card,)
        t = rng.dirichlet(np.ones(v.card),
                          size=shape[:-1] or (1,)).reshape(shape)
        cpds[v.name] = MultinomialCPD(jnp.asarray(t))
    return BayesianNetwork(dag, cpds), xs


@pytest.fixture(scope="module")
def clg_net():
    vs = Variables()
    Z = vs.new_multinomial("Z", 2)
    X1 = vs.new_gaussian("X1")
    X2 = vs.new_gaussian("X2")
    dag = DAG(vs)
    dag.add_parent(X1, Z)
    dag.add_parent(X2, Z)
    cpds = {
        "Z": MultinomialCPD(jnp.array([0.3, 0.7])),
        "X1": CLGCPD(alpha=jnp.array([0.0, 4.0]), beta=jnp.zeros((2, 0)),
                     sigma2=jnp.array([1.0, 1.0])),
        "X2": CLGCPD(alpha=jnp.array([-2.0, 2.0]), beta=jnp.zeros((2, 0)),
                     sigma2=jnp.array([0.5, 2.0])),
    }
    return BayesianNetwork(dag, cpds), Z, X1, X2


# -- acceptance criterion: marginals match brute force on random nets --------


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 7])
def test_jt_matches_brute_force(seed):
    bn, xs = random_discrete_bn(seed)
    for evidence in ({}, {"V1": 1, "V4": 0}):
        eng = JunctionTreeEngine(bn)
        eng.set_evidence(evidence)
        eng.run_inference()
        for v in xs:
            if v.name in evidence:
                continue
            got = np.asarray(eng.posterior_discrete(v))
            exp = np.asarray(brute_posterior(bn, v, evidence))
            np.testing.assert_allclose(got, exp, atol=1e-6)


def test_jt_log_evidence_matches_brute_force():
    from repro.infer_exact.brute import brute_log_evidence

    bn, xs = random_discrete_bn(2)
    ev = {"V0": 1, "V5": 0}
    eng = JunctionTreeEngine(bn)
    eng.set_evidence(ev)
    eng.run_inference()
    np.testing.assert_allclose(float(eng.log_evidence()),
                               float(brute_log_evidence(bn, ev)), atol=1e-5)


# -- chain models: the factored-frontier C=1 exact-HMM oracle ----------------


def test_jt_matches_hmm_forward_on_chain():
    T, S, V = 6, 3, 4
    rng = np.random.RandomState(3)
    init = rng.dirichlet(np.ones(S))
    trans = rng.dirichlet(np.ones(S), size=S)        # [S, S]
    emit = rng.dirichlet(np.ones(V), size=S)         # [S, V]
    obs = rng.randint(0, V, T)

    vs = Variables()
    hs = [vs.new_multinomial(f"H{t}", S) for t in range(T)]
    os_ = [vs.new_multinomial(f"O{t}", V) for t in range(T)]
    dag = DAG(vs)
    cpds = {"H0": MultinomialCPD(jnp.asarray(init))}
    for t in range(1, T):
        dag.add_parent(hs[t], hs[t - 1])
        cpds[f"H{t}"] = MultinomialCPD(jnp.asarray(trans))
    for t in range(T):
        dag.add_parent(os_[t], hs[t])
        cpds[f"O{t}"] = MultinomialCPD(jnp.asarray(emit))
    bn = BayesianNetwork(dag, cpds)

    evidence = {f"O{t}": int(obs[t]) for t in range(T)}
    eng = JunctionTreeEngine(bn)
    eng.set_evidence(evidence)
    eng.run_inference()
    got = np.asarray(eng.posterior_discrete(hs[-1]))

    # exact reference: float64 forward recursion
    a = init * emit[:, obs[0]]
    a = a / a.sum()
    for t in range(1, T):
        a = (a @ trans) * emit[:, obs[t]]
        a = a / a.sum()
    np.testing.assert_allclose(got, a, atol=1e-5)

    # the in-repo C=1 factored-frontier oracle (float32 scan) agrees too
    loglik = jnp.log(jnp.asarray(emit[:, obs].T))    # [T, S]
    beliefs, _ = hmm_forward(jnp.asarray(init), jnp.asarray(trans), loglik)
    # filtered == smoothed at the final step == JT marginal of H_{T-1}
    np.testing.assert_allclose(got, np.asarray(beliefs[-1]), atol=1e-3)


# -- CLG conditioning ---------------------------------------------------------


def test_jt_clg_closed_form(clg_net):
    bn, Z, X1, X2 = clg_net
    eng = JunctionTreeEngine(bn)
    eng.set_evidence({"X1": 3.0, "X2": 1.0})
    eng.run_inference()

    def npdf(x, m, s2=1.0):
        return np.exp(-0.5 * (x - m) ** 2 / s2) / np.sqrt(2 * np.pi * s2)

    l0 = 0.3 * npdf(3, 0) * npdf(1, -2, 0.5)
    l1 = 0.7 * npdf(3, 4) * npdf(1, 2, 2.0)
    exact = np.array([l0, l1]) / (l0 + l1)
    np.testing.assert_allclose(np.asarray(eng.posterior_discrete(Z)), exact,
                               atol=1e-6)
    np.testing.assert_allclose(float(eng.log_evidence()), np.log(l0 + l1),
                               atol=1e-5)


def test_jt_continuous_posterior_mixture(clg_net):
    bn, Z, X1, X2 = clg_net
    eng = JunctionTreeEngine(bn)
    eng.set_evidence({"X1": 3.0})
    eng.run_inference()
    m, v = eng.posterior_mean_var(X2)

    def npdf(x, mu):
        return np.exp(-0.5 * (x - mu) ** 2) / np.sqrt(2 * np.pi)

    w = np.array([0.3 * npdf(3, 0), 0.7 * npdf(3, 4)])
    w = w / w.sum()
    mu = np.array([-2.0, 2.0])
    s2 = np.array([0.5, 2.0])
    em = (w * mu).sum()
    ev = (w * (s2 + mu ** 2)).sum() - em ** 2
    np.testing.assert_allclose(float(m), em, atol=1e-5)
    np.testing.assert_allclose(float(v), ev, atol=1e-5)


def test_jt_regression_parent_conditioning():
    """Observed continuous parent feeds the child's lambda analytically."""
    vs = Variables()
    Z = vs.new_multinomial("Z", 2)
    X1 = vs.new_gaussian("X1")
    X2 = vs.new_gaussian("X2")
    dag = DAG(vs)
    dag.add_parent(X1, Z)
    dag.add_parent(X2, Z)
    dag.add_parent(X2, X1)
    bn = BayesianNetwork(dag, {
        "Z": MultinomialCPD(jnp.array([0.4, 0.6])),
        "X1": CLGCPD(jnp.array([0.0, 4.0]), jnp.zeros((2, 0)),
                     jnp.array([1.0, 1.0])),
        "X2": CLGCPD(jnp.array([1.0, -1.0]), jnp.array([[0.5], [2.0]]),
                     jnp.array([1.0, 1.0]))})
    eng = JunctionTreeEngine(bn)
    eng.set_evidence({"X1": 2.0, "X2": 1.5})
    eng.run_inference()

    def npdf(x, m):
        return np.exp(-0.5 * (x - m) ** 2) / np.sqrt(2 * np.pi)

    l0 = 0.4 * npdf(2, 0) * npdf(1.5, 2.0)
    l1 = 0.6 * npdf(2, 4) * npdf(1.5, 3.0)
    exact = np.array([l0, l1]) / (l0 + l1)
    np.testing.assert_allclose(np.asarray(eng.posterior_discrete(Z)), exact,
                               atol=1e-6)
    # unobserved continuous parent of an observed node: the strong junction
    # tree integrates X1 out exactly (this used to raise NotImplementedError)
    eng2 = JunctionTreeEngine(bn)
    eng2.set_evidence({"X2": 1.5})
    eng2.run_inference()
    np.testing.assert_allclose(
        np.asarray(eng2.posterior_discrete(Z)),
        np.asarray(brute_posterior(bn, Z, {"X2": 1.5})), atol=1e-5)
    m, v = eng2.posterior_mean_var(X1)
    from repro.infer_exact import brute_posterior_mean_var

    mb, vb = brute_posterior_mean_var(bn, X1, {"X2": 1.5})
    np.testing.assert_allclose(float(m), float(mb), atol=1e-5)
    np.testing.assert_allclose(float(v), float(vb), atol=1e-5)


# -- batching: many evidence instances in one device call --------------------


def test_jt_batched_evidence_matches_per_instance():
    bn, xs = random_discrete_bn(1)
    vals = np.array([0, 1, 1, 0])
    eng = JunctionTreeEngine(bn)
    eng.set_evidence({"V2": vals})
    eng.run_inference()
    batch = np.asarray(eng.posterior_discrete(xs[0]))
    assert batch.shape[0] == 4
    for b, v in enumerate(vals):
        e = JunctionTreeEngine(bn)
        e.set_evidence({"V2": int(v)})
        e.run_inference()
        np.testing.assert_allclose(batch[b],
                                   np.asarray(e.posterior_discrete(xs[0])),
                                   atol=1e-6)


def test_jt_pallas_path_matches_jnp():
    bn, xs = random_discrete_bn(4)
    ev = {"V1": np.array([0, 1, 0]), "V3": np.array([1, 1, 0])}
    ref_eng = JunctionTreeEngine(bn, use_pallas=False)
    ref_eng.set_evidence(ev)
    ref_eng.run_inference()
    pal = JunctionTreeEngine(bn, use_pallas=True)
    pal.set_evidence(ev)
    pal.run_inference()
    for v in xs:
        np.testing.assert_allclose(np.asarray(pal.posterior_discrete(v)),
                                   np.asarray(ref_eng.posterior_discrete(v)),
                                   atol=1e-5)


# -- compilation structure ---------------------------------------------------


def test_junction_tree_structure_and_rip():
    bn, _ = random_discrete_bn(5, n=6, p_edge=0.6)
    jt = compile_junction_tree(bn)
    assert len(jt.edges) == len(jt.cliques) - 1          # a tree
    verify_running_intersection(jt.cliques, jt.edges)    # no raise
    names = {v.name for v in bn.order if v.is_discrete}
    assert set().union(*jt.cliques) == names             # covers all vars
    for (a, b), s in zip(jt.edges, jt.sepsets):
        assert s == jt.cliques[a] & jt.cliques[b]


def test_rip_checker_catches_violation():
    cliques = [frozenset("ab"), frozenset("bc"), frozenset("ad")]
    # 'a' appears in cliques 0 and 2, but the path 0-1-2 drops it at 1
    with pytest.raises(AssertionError):
        verify_running_intersection(cliques, [(0, 1), (1, 2)])


# -- model-layer wiring ------------------------------------------------------


def test_posterior_exact_matches_vmp_on_gmm():
    from repro.data.synthetic import gmm_stream
    from repro.pgm_models import GaussianMixture

    s, _, _ = gmm_stream(600, 3, 4, seed=1)
    m = GaussianMixture(s.attributes, n_states=3)
    m.update_model(s)
    batch = s.collect()
    rz = np.asarray(m.posterior_z(batch))
    re = np.asarray(m.posterior_exact(batch))
    assert re.shape == rz.shape
    np.testing.assert_allclose(re, rz, atol=1e-3)
    np.testing.assert_allclose(re.sum(-1), 1.0, atol=1e-5)


def test_pgm_query_engine_schema_batching(clg_net):
    from repro.serve.engine import PGMQueryEngine

    bn, Z, X1, X2 = clg_net
    eng = PGMQueryEngine(bn, mode="exact")
    q1 = eng.submit("Z", {"X1": 3.0, "X2": 1.0})
    q2 = eng.submit("Z", {"X1": -1.0, "X2": 0.0})
    q3 = eng.submit("Z", {"X1": 3.0})          # different schema
    done = eng.flush()
    assert len(done) == 3 and all(q.done for q in done)
    assert not eng._queue
    # row 1 of the batched group == a fresh single query
    single = JunctionTreeEngine(bn)
    single.set_evidence({"X1": -1.0, "X2": 0.0})
    single.run_inference()
    np.testing.assert_allclose(q2.result,
                               np.asarray(single.posterior_discrete(Z)),
                               atol=1e-6)
    assert q1.log_evidence is not None and q3.log_evidence is not None


def test_pgm_query_engine_vmp_mode():
    """mode='vmp' serves q(Z | x) from a fitted plate model through the
    jitted posterior_z path — one compiled dispatch per schema group."""
    from repro.data.synthetic import gmm_stream
    from repro.pgm_models import GaussianMixture
    from repro.serve.engine import PGMQueryEngine

    s, _, _ = gmm_stream(600, 3, 4, seed=1)
    m = GaussianMixture(s.attributes, n_states=3)
    m.update_model(s)
    batch = s.collect()
    eng = PGMQueryEngine(m, mode="vmp")
    qs = [eng.submit("Z", {f"X{i}": float(batch.xc[b, i]) for i in range(4)})
          for b in range(5)]
    done = eng.flush()
    assert len(done) == 5 and all(q.done for q in done)
    expect = np.asarray(m.posterior_z(batch))[:5]
    got = np.stack([q.result for q in qs])
    np.testing.assert_allclose(got, expect, atol=1e-5)

    # malformed queries are rejected at submit — the queue is untouched,
    # so a later flush() cannot drop valid queued work
    with pytest.raises(ValueError, match="missing"):
        eng.submit("Z", {"X0": 0.0})
    with pytest.raises(ValueError, match="latent Z"):
        eng.submit("X0", {f"X{i}": 0.0 for i in range(4)})
    assert not eng._queue


# -- DAG.add_parent hardening -------------------------------------------------


def test_dag_rejects_duplicate_edge():
    vs = Variables()
    a = vs.new_multinomial("A", 2)
    b = vs.new_multinomial("B", 2)
    dag = DAG(vs)
    dag.add_parent(b, a)
    with pytest.raises(ValueError, match="duplicate"):
        dag.add_parent(b, a)
    assert len(dag.get_parents(b)) == 1


def test_dag_rejects_cycle_and_stays_valid():
    vs = Variables()
    a = vs.new_multinomial("A", 2)
    b = vs.new_multinomial("B", 2)
    c = vs.new_multinomial("C", 2)
    dag = DAG(vs)
    dag.add_parent(b, a)
    dag.add_parent(c, b)
    with pytest.raises(ValueError, match="cycle"):
        dag.add_parent(a, c)
    # failed insert left the graph untouched and acyclic
    assert dag.get_parents(a) == []
    assert [v.name for v in dag.topological_order()] == ["A", "B", "C"]


def test_dag_self_loop():
    vs = Variables()
    a = vs.new_multinomial("A", 2)
    dag = DAG(vs)
    with pytest.raises(ValueError, match="self-loop"):
        dag.add_parent(a, a)


# -- evidence validation ------------------------------------------------------


def test_jt_rejects_bad_evidence(clg_net):
    bn, Z, X1, X2 = clg_net
    eng = JunctionTreeEngine(bn)
    with pytest.raises(ValueError, match="unknown evidence"):
        eng.set_evidence({"X9": 1.0})
    with pytest.raises(ValueError, match="outside"):
        eng.set_evidence({"Z": 7})
    eng.set_evidence({"X1": np.array([1.0, 2.0]),
                      "X2": np.array([0.0, 1.0, 2.0])})
    with pytest.raises(ValueError, match="batch lengths"):
        eng.run_inference()


def test_jt_impossible_evidence_flags_neg_inf():
    vs = Variables()
    a = vs.new_multinomial("A", 2)
    b = vs.new_multinomial("B", 2)
    dag = DAG(vs)
    dag.add_parent(b, a)
    bn = BayesianNetwork(dag, {
        "A": MultinomialCPD(jnp.array([1.0, 0.0])),
        "B": MultinomialCPD(jnp.array([[1.0, 0.0], [0.5, 0.5]]))})
    eng = JunctionTreeEngine(bn)
    eng.set_evidence({"B": 1})
    eng.run_inference()
    assert np.isneginf(float(eng.log_evidence()))


def test_jt_batched_continuous_query_no_discrete_parents():
    """posterior_mean_var under batched evidence when the queried node's
    only parent is continuous (regression: B was taken from a placeholder)."""
    vs = Variables()
    Z = vs.new_multinomial("Z", 2)
    X1 = vs.new_gaussian("X1")
    X2 = vs.new_gaussian("X2")
    dag = DAG(vs)
    dag.add_parent(X1, Z)
    dag.add_parent(X2, X1)
    bn = BayesianNetwork(dag, {
        "Z": MultinomialCPD(jnp.array([0.5, 0.5])),
        "X1": CLGCPD(jnp.array([0.0, 4.0]), jnp.zeros((2, 0)),
                     jnp.array([1.0, 1.0])),
        "X2": CLGCPD(jnp.asarray(1.0), jnp.asarray([2.0]),
                     jnp.asarray(0.5))})
    ev = np.array([0.0, 2.0, 4.0])
    eng = JunctionTreeEngine(bn)
    eng.set_evidence({"X1": ev})
    eng.run_inference()
    m, v = eng.posterior_mean_var(X2)
    np.testing.assert_allclose(np.asarray(m), 1.0 + 2.0 * ev, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v), 0.5, atol=1e-6)
