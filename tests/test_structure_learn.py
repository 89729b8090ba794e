"""Structure learning: family scores, Chow-Liu/TAN, hill-climbing, drift
re-search (repro.learn_structure) — recovery asserted against the
ground-truth generators in data.synthetic."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import synthetic as syn
from repro.data.stream import Attribute, DataStream, FINITE, REAL
from repro.learn_structure import (AdaptiveStructure, chow_liu, fit_cpds,
                                   hill_climb, nig_evidence, predict_class,
                                   tan)
from repro.learn_structure import scores as S
from repro.learn_structure.metrics import skeleton_f1, undirected_edges


# ---------------------------------------------------------------------------
# scores
# ---------------------------------------------------------------------------


def test_bdeu_matches_naive_enumeration():
    """The batched BDeu path (family_counts kernel + vectorized lgamma
    algebra) against a per-cell Python enumeration."""
    rng = np.random.default_rng(1)
    N, cards, ess = 300, [2, 3, 2], 1.0
    xd = jnp.asarray(np.stack([rng.integers(0, c, N) for c in cards],
                              1).astype(np.int32))
    fams = [(0, (1,)), (1, ()), (2, (0, 1))]
    got = S.disc_family_scores(xd, fams, cards, ess=ess)

    xnp = np.asarray(xd)
    for m, (ch, pa) in enumerate(fams):
        r = cards[ch]
        q = int(np.prod([cards[p] for p in pa])) if pa else 1
        a_j, a_jk = ess / q, ess / (q * r)
        cnt = {}
        for row in xnp:
            j = 0
            for p in pa:
                j = j * cards[p] + row[p]
            cnt[(j, row[ch])] = cnt.get((j, row[ch]), 0) + 1
        exp = 0.0
        for j in range(q):
            nij = sum(cnt.get((j, k), 0) for k in range(r))
            exp += math.lgamma(a_j) - math.lgamma(a_j + nij)
            for k in range(r):
                exp += (math.lgamma(a_jk + cnt.get((j, k), 0))
                        - math.lgamma(a_jk))
        assert abs(float(got[m]) - exp) < 1e-3


def test_disc_family_scores_backend_parity():
    rng = np.random.default_rng(2)
    cards = [3, 2, 4, 3]
    xd = jnp.asarray(np.stack([rng.integers(0, c, 800) for c in cards],
                              1).astype(np.int32))
    fams = [(i, tuple(j for j in range(4) if j != i)[:2]) for i in range(4)]
    fams += [(0, ()), (2, (1,))]
    a = S.disc_family_scores(xd, fams, cards, backend="einsum")
    b = S.disc_family_scores(xd, fams, cards, backend="pallas")
    np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-5)


def test_nig_evidence_matches_sequential_predictive():
    """Closed-form NIG evidence == prequential product of posterior-
    predictive student-t densities (the textbook identity)."""
    rng = np.random.default_rng(3)
    D, N = 3, 40
    X = rng.standard_normal((N, D))
    X[:, 0] = 1.0
    y = X @ rng.standard_normal(D) + 0.5 * rng.standard_normal(N)
    kappa, a0, b0 = 2.0, 1.5, 0.8
    ev = float(nig_evidence(jnp.asarray(X.T @ X), jnp.asarray(X.T @ y),
                            jnp.asarray(y @ y), jnp.asarray(float(N)),
                            kappa=kappa, a0=a0, b0=b0))

    def t_logpdf(x, df, loc, scale):
        z = (x - loc) / scale
        return (math.lgamma((df + 1) / 2) - math.lgamma(df / 2)
                - 0.5 * math.log(df * math.pi) - math.log(scale)
                - (df + 1) / 2 * math.log1p(z * z / df))

    K, m, a, b = kappa * np.eye(D), np.zeros(D), a0, b0
    lp = 0.0
    for i in range(N):
        x_, y_ = X[i], y[i]
        s2 = b / a * (1 + x_ @ np.linalg.solve(K, x_))
        lp += t_logpdf(y_, 2 * a, x_ @ m, math.sqrt(s2))
        Kn = K + np.outer(x_, x_)
        mn = np.linalg.solve(Kn, K @ m + x_ * y_)
        b = b + 0.5 * (y_ * y_ + m @ K @ m - mn @ Kn @ mn)
        K, m, a = Kn, mn, a + 0.5
    assert abs(ev - lp) < 1e-3


def test_nig_evidence_zero_padding_invariant():
    """Zero-padded design columns leave the evidence unchanged — the
    property that lets ragged candidate sets batch into one kernel call."""
    rng = np.random.default_rng(4)
    X = rng.standard_normal((60, 2))
    y = X @ [1.0, -0.5] + 0.3 * rng.standard_normal(60)
    args = (jnp.asarray(X.T @ X), jnp.asarray(X.T @ y), jnp.asarray(y @ y),
            jnp.asarray(60.0))
    ev = float(nig_evidence(*args, kappa=1.3))
    pad = (jnp.asarray(np.pad(X.T @ X, ((0, 3), (0, 3)))),
           jnp.asarray(np.pad(X.T @ y, (0, 3))), args[2], args[3])
    ev_pad = float(nig_evidence(*pad, kappa=1.3))
    assert abs(ev - ev_pad) < 1e-4


def test_clg_scores_prefer_true_parent():
    bn = syn.clg_tree_bn(5, seed=2)
    s = syn.bn_stream(bn, 4000, seed=3)
    b = s.collect()
    cards = []
    # the true parent must beat the empty family and (data-processing
    # inequality) every node whose tree path to the child runs THROUGH the
    # parent — i.e. the parent's other neighbors.  Nodes on the child's
    # descendant side can legitimately score higher: scores identify the
    # skeleton, not the orientation.
    adj = {int(c[1:]): set() for c in bn.dag.parents}
    for c, ps in bn.dag.parents.items():
        for p in ps:
            adj[int(c[1:])].add(int(p.name[1:]))
            adj[int(p.name[1:])].add(int(c[1:]))
    for child, ps in bn.dag.parents.items():
        if not ps:
            continue
        ci = int(child[1:])
        p = int(ps[0].name[1:])
        others = sorted(adj[p] - {ci})
        fams = ([(ci, (p,), ()), (ci, (), ())]
                + [(ci, (o,), ()) for o in others])
        sc = S.clg_family_scores(b.xc, b.xd, fams, cards)
        assert sc[0] == max(sc), (child, sc)


# ---------------------------------------------------------------------------
# Chow-Liu / TAN
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 3])
def test_chowliu_exact_discrete_tree_recovery(seed):
    """Acceptance: Chow-Liu exactly recovers a ground-truth tree from
    ample synthetic data."""
    bn = syn.random_discrete_bn(7, card=3, seed=seed, tree=True)
    stream = syn.bn_stream(bn, 6000, seed=seed + 100)
    edges, learned = chow_liu(stream, stream.attributes)
    assert undirected_edges(edges) == undirected_edges(bn)
    # the fitted network reproduces the generator's conditionals closely
    asg = {a.name: stream.collect().xd[:, i]
           for i, a in enumerate(stream.attributes)}
    lp_true = float(jnp.mean(bn.log_prob(asg)))
    lp_learn = float(jnp.mean(learned.log_prob(asg)))
    assert lp_learn > lp_true - 0.05


def test_chowliu_exact_clg_tree_recovery():
    bn = syn.clg_tree_bn(8, seed=5)
    stream = syn.bn_stream(bn, 8000, seed=2)
    edges, learned = chow_liu(stream, stream.attributes)
    assert undirected_edges(edges) == undirected_edges(bn)
    asg = {a.name: stream.collect().xc[:, i]
           for i, a in enumerate(stream.attributes)}
    assert np.isfinite(np.asarray(learned.log_prob(asg))).all()


def test_chowliu_rejects_mixed_features():
    attrs = [Attribute("G0", REAL), Attribute("D0", FINITE, 2)]
    s = DataStream.from_arrays(attrs, np.zeros((4, 1), np.float32),
                               np.zeros((4, 1), np.int32))
    with pytest.raises(ValueError, match="mixed"):
        chow_liu(s, attrs)


def test_chowliu_rejects_out_of_range_root():
    attrs = [Attribute("G0", REAL), Attribute("G1", REAL)]
    s = DataStream.from_arrays(attrs, np.zeros((8, 2), np.float32))
    with pytest.raises(ValueError, match="root"):
        chow_liu(s, attrs, root=2)


def test_tan_recovers_augmenting_tree_and_classifies():
    """TAN on data generated from a TAN structure: class -> all features,
    plus a feature chain X0 -> X1 -> X2; conditional-MI MST must find the
    chain, and the classifier must beat the class prior."""
    import jax

    from repro.core.dag import (BayesianNetwork, DAG, MultinomialCPD,
                                Variables)

    rng = np.random.default_rng(0)
    card, ncls = 3, 2
    vs = Variables()
    Y = vs.new_multinomial("Y", ncls)
    xs = [vs.new_multinomial(f"X{i}", card) for i in range(3)]
    dag = DAG(vs)
    for x in xs:
        dag.add_parent(x, Y)
    dag.add_parent(xs[1], xs[0])
    dag.add_parent(xs[2], xs[1])

    def sharp(q):
        t = 0.15 * rng.dirichlet(np.ones(card), size=q)
        for j in range(q):
            t[j, j % card] += 0.85
        return t

    cpds = {"Y": MultinomialCPD(jnp.asarray([0.6, 0.4]))}
    cpds["X0"] = MultinomialCPD(jnp.asarray(
        sharp(ncls).astype(np.float32)))
    for i in (1, 2):
        t = sharp(ncls * card).reshape(ncls, card, card)
        cpds[f"X{i}"] = MultinomialCPD(jnp.asarray(t.astype(np.float32)))
    bn = BayesianNetwork(dag, cpds)
    stream = syn.bn_stream(bn, 6000, seed=7)

    edges, learned = tan(stream, stream.attributes, "Y")
    got = {e for e in edges if "Y" not in e}
    assert undirected_edges(got) == {frozenset(("X0", "X1")),
                                frozenset(("X1", "X2"))}
    # every feature keeps the class parent
    for i in range(3):
        assert ("Y", f"X{i}") in edges

    batch = stream.collect()
    ycol = [a.name for a in stream.attributes
            if a.kind == FINITE].index("Y")
    pred = np.asarray(predict_class(learned, "Y", batch, stream.attributes))
    acc = (pred == np.asarray(batch.xd)[:, ycol]).mean()
    assert acc > 0.85


# ---------------------------------------------------------------------------
# hill-climbing
# ---------------------------------------------------------------------------


def test_hillclimb_recovers_discrete_skeleton():
    """Acceptance: F1 >= 0.9 on a bounded-fan-in random discrete BN."""
    f1s = []
    for seed in (0, 2):
        bn = syn.random_discrete_bn(6, card=3, max_parents=2, seed=seed)
        stream = syn.bn_stream(bn, 6000, seed=seed + 50)
        res = hill_climb(stream, stream.attributes, max_parents=2)
        f1s.append(skeleton_f1(undirected_edges(bn), undirected_edges(res.parents)))
    assert min(f1s) >= 0.9, f1s


def test_hillclimb_recovers_clg_tree_exactly():
    bn = syn.clg_tree_bn(6, seed=7)
    stream = syn.bn_stream(bn, 6000, seed=9)
    res = hill_climb(stream, stream.attributes, max_parents=2)
    assert undirected_edges(res.parents) == undirected_edges(bn)
    assert res.bn is not None


def test_hillclimb_respects_fan_in_and_clg_restriction():
    bn = syn.random_discrete_bn(5, card=2, max_parents=2, seed=1)
    stream = syn.bn_stream(bn, 2000, seed=4)
    res = hill_climb(stream, stream.attributes, max_parents=1)
    assert all(len(p) <= 1 for p in res.parents.values())
    # mixed data: discrete children must never gain continuous parents
    mbn = syn.clg_tree_bn(3, seed=0)
    ms = syn.bn_stream(mbn, 1500, seed=1)
    joint = DataStream.from_arrays(
        ms.attributes + [Attribute("D0", FINITE, 2)],
        np.asarray(ms.collect().xc),
        np.asarray(np.random.default_rng(0).integers(0, 2, (1500, 1)),
                   np.int32))
    res2 = hill_climb(joint, joint.attributes, max_parents=2)
    for child, ps in res2.parents.items():
        if child.startswith("D"):
            assert all(p.startswith("D") for p in ps)


def test_hillclimb_score_caching_and_monotone_trace():
    bn = syn.random_discrete_bn(5, card=2, max_parents=2, seed=3)
    stream = syn.bn_stream(bn, 3000, seed=6)
    res = hill_climb(stream, stream.attributes, max_parents=2)
    # every applied operator improved the score
    assert all(d > 0 for *_, d in res.trace)
    # cache-miss count stays far below ops * iters re-scoring
    assert res.n_scored < 5 * 2 ** 4 * max(res.n_iters, 1)


# ---------------------------------------------------------------------------
# materialization -> inference engines
# ---------------------------------------------------------------------------


def test_fit_cpds_recovers_tables():
    bn = syn.random_discrete_bn(4, card=3, seed=5, tree=True)
    stream = syn.bn_stream(bn, 20_000, seed=8)
    parents = {c: [p.name for p in ps]
               for c, ps in bn.dag.parents.items()}
    learned = fit_cpds(stream.attributes, parents, stream.collect())
    for name, cpd in bn.cpds.items():
        np.testing.assert_allclose(
            np.asarray(learned.cpds[name].table), np.asarray(cpd.table),
            atol=0.05)


@pytest.mark.parametrize("n_vars,seed,n_rows,evidence", [
    (5, 0, 12_000, [{"D3": 1, "D4": 2}]),
    # four queries of one evidence schema, answered as one bucket
    (6, 3, 4_000, [{"D2": k % 3, "D3": (k + 1) % 3} for k in range(4)]),
])
def test_learned_bn_serves_exact_queries(n_vars, seed, n_rows, evidence):
    """The learned network drops into infer_exact / PGMQueryEngine and its
    answers match the generator's on the same junction tree."""
    from repro.infer_exact import JunctionTreeEngine
    from repro.serve.engine import PGMQueryEngine

    bn = syn.random_discrete_bn(n_vars, card=3, seed=seed, tree=True)
    stream = syn.bn_stream(bn, n_rows, seed=seed + 1)
    edges, learned = chow_liu(stream, stream.attributes)
    assert undirected_edges(edges) == undirected_edges(bn)

    eng = PGMQueryEngine(learned, mode="exact")
    qs = [eng.submit("D0", ev) for ev in evidence]
    eng.flush()
    for q, ev in zip(qs, evidence):
        assert q.done and q.result.shape == (3,)
        np.testing.assert_allclose(q.result.sum(), 1.0, atol=1e-5)

        ref = JunctionTreeEngine(bn)
        ref.set_evidence(ev)
        ref.run_inference()
        exact = np.asarray(ref.posterior_discrete(
            bn.dag.variables.by_name("D0")))
        np.testing.assert_allclose(q.result, exact, atol=0.06)


# ---------------------------------------------------------------------------
# drift-triggered re-search (stream_adapt)
# ---------------------------------------------------------------------------


def test_drift_triggers_structure_switch():
    """Acceptance: the generating network changes mid-stream; the PH
    monitor fires, the window resets, and the re-searched structure
    matches the new generator — with the relearned BayesianNetwork
    answering queries through the exact engine unchanged."""
    from repro.serve.engine import PGMQueryEngine

    bn_a = syn.random_discrete_bn(5, card=3, seed=0, tree=True)
    bn_b = syn.random_discrete_bn(5, card=3, seed=11, tree=True)
    ea, eb = undirected_edges(bn_a), undirected_edges(bn_b)
    assert ea != eb                   # the concept switch is observable
    stream = DataStream.concat([syn.bn_stream(bn_a, 6000, seed=1),
                                syn.bn_stream(bn_b, 6000, seed=2)])

    ad = AdaptiveStructure(stream.attributes, learner="chowliu",
                           window=4000, drift_threshold=3.0)
    drift_batches, structures = [], {}
    for i, b in enumerate(stream.batches(500)):
        info = ad.update(b)
        structures[i] = undirected_edges({(u, v) for u, v in ad.edges()})
        if info["drifted"]:
            drift_batches.append(i)
    assert drift_batches and drift_batches[0] >= 12   # not before the switch
    assert ad.n_drifts >= 1
    assert structures[10] == ea                       # pre-drift: concept A
    assert structures[max(structures)] == eb          # post-drift: concept B

    eng = PGMQueryEngine(ad.bn, mode="exact")
    q = eng.submit("D0", {"D1": 0})
    eng.flush()
    assert q.done and abs(float(q.result.sum()) - 1.0) < 1e-5


def test_adaptive_structure_hillclimb_learner_smoke():
    bn = syn.random_discrete_bn(4, card=2, seed=2, tree=True)
    stream = syn.bn_stream(bn, 3000, seed=5)
    # relearn_every exercises the scheduled re-search path, including the
    # stats-reuse shortcut when the search keeps the structure unchanged
    ad = AdaptiveStructure(stream.attributes, learner="hillclimb",
                           window=3000, max_parents=2, relearn_every=2)
    ad.fit_stream(stream, batch_size=750)
    assert ad.bn is not None and ad.n_relearn >= 2
    assert skeleton_f1(undirected_edges(bn), undirected_edges(ad.parents)) >= 0.5


def test_incremental_refit_matches_one_shot_fit():
    """The streaming CPD refit (sum of per-chunk structure_stats) must
    equal fit_cpds on the concatenated window — the additivity that makes
    per-batch cost O(batch) instead of O(window).  Non-default ``ess``
    checks the refit and the relearn share one smoothing regime."""
    bn = syn.random_discrete_bn(4, card=3, seed=6, tree=True)
    stream = syn.bn_stream(bn, 4000, seed=7)
    ad = AdaptiveStructure(stream.attributes, learner="chowliu",
                           window=4000, ess=5.0)
    for b in stream.batches(500):
        ad.update(b)
    oneshot = fit_cpds(stream.attributes,
                       {k: list(v) for k, v in ad.parents.items()},
                       ad._window_batch(), ess=5.0)
    for name, cpd in oneshot.cpds.items():
        np.testing.assert_allclose(np.asarray(ad.bn.cpds[name].table),
                                   np.asarray(cpd.table), atol=1e-5)


def test_adaptive_structure_rejects_bad_config():
    attrs = [Attribute("D0", FINITE, 2)]
    with pytest.raises(ValueError, match="unknown learner"):
        AdaptiveStructure(attrs, learner="magic")
    with pytest.raises(ValueError, match="class_name"):
        AdaptiveStructure(attrs, learner="tan")


# ---------------------------------------------------------------------------
# satellites riding along
# ---------------------------------------------------------------------------


def test_topological_order_deep_chain_iterative():
    """Structure search generates deep chains; topological_order must not
    hit Python's recursion limit (it used to at ~330 nodes)."""
    from repro.core.dag import DAG, Variables

    n = 3000
    vs = Variables()
    nodes = [vs.new_multinomial(f"V{i}", 2) for i in range(n)]
    dag = DAG(vs)
    for a, b in zip(nodes, nodes[1:]):
        dag.add_parent(b, a)
    order = dag.topological_order()
    assert [v.name for v in order] == [f"V{i}" for i in range(n)]
    # cycle detection still works on the iterative path
    dag.parents["V0"].append(nodes[-1])
    with pytest.raises(ValueError, match="cycle"):
        dag.topological_order()


def test_datastream_concat_rejects_schema_mismatch():
    a1 = [Attribute("X", REAL)]
    a2 = [Attribute("X", FINITE, 2)]
    s1 = DataStream.from_arrays(a1, np.zeros((3, 1), np.float32))
    s2 = DataStream.from_arrays(a2, np.zeros((3, 0), np.float32),
                                np.zeros((3, 1), np.int32))
    with pytest.raises(ValueError, match="schema"):
        DataStream.concat([s1, s2])
    with pytest.raises(ValueError, match="zero"):
        DataStream.concat([])
    # matching schemas still concatenate
    s3 = DataStream.from_arrays(a1, np.ones((2, 1), np.float32))
    cat = DataStream.concat([s1, s3])
    assert cat.collect().xc.shape == (5, 1)
