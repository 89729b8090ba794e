#!/usr/bin/env bash
# Tier-1 CI runner (run by .github/workflows/ci.yml on every push/PR, and by
# hand via `bash scripts/ci.sh`).  Gates:
#   1. the full pytest suite with -x (any collection error — e.g. a jax
#      import that moved between versions — fails fast instead of landing),
#   2. kernel interpret-vs-policy parity: tests/test_kernels.py runs once
#      with REPRO_PALLAS_INTERPRET=1 forced and once under the default
#      policy, so on a TPU runner the compiled Mosaic path is checked
#      against the same oracles the CPU container verifies in interpret
#      mode (they may not silently diverge),
#   3. the streaming perf harness in --json mode on tiny sizes with schema
#      validation, so perf-trajectory breakage (BENCH_streaming.json) fails
#      tier-1 instead of silently rotting,
#   3b. the perf-regression gate: scripts/bench_compare.py diffs the fresh
#      streaming / serve / resilience smoke payloads against the committed
#      BENCH_*.json baselines using scale-robust tolerance bands
#      (dimensionless within-run ratios, load-matched rows, boolean
#      invariants — the bands are documented in the script docstring), so
#      an out-of-band perf drift fails tier-1 with a named check,
#   4. the d-VMP mesh-path harness (--json --dvmp) on a forced 4-device
#      host mesh with schema + shard-invariance validation,
#   4b. the latent-path harness (--json --latent) on tiny sizes: schema
#      validation PLUS the fused-kernel-vs-einsum and bucketed-vs-per-clique
#      parity gates baked into the validator (the latent-kernel interpret-
#      vs-policy parity itself rides the test_kernels legs of step 2),
#   4c. the structure-learning harness (--json --structure) on tiny sizes:
#      schema validation PLUS the family_counts-vs-einsum score parity and
#      the Chow-Liu / hill-climb recovery gates baked into the validator,
#   4d. the temporal harness (--json --temporal) on tiny sizes: schema
#      validation PLUS the fused-vs-host-loop posterior parity, the fHMM
#      pallas-vs-einsum suff-stats parity and the no-retrace program-cache
#      flag baked into the validator,
#   4e. the serving harness (--json --serve) on short offered-load windows
#      over a forced 4-device host: schema validation PLUS the single-device
#      and mesh-replica drivers, two load points each, and the
#      hot-swap-zero-drop gate baked into the validator,
#   4f. the resilience harness (--json --resilience) on tiny sizes: schema
#      validation PLUS the quarantine bit-identity, serve-zero-loss (worker
#      crash + compile failure under load) and bit-identical-resume gates
#      baked into the validator,
#   5. end-to-end junction-tree queries through the public API: a discrete
#      2-variable query AND a strong-junction-tree query on a CLG network
#      with an unobserved continuous INTERNAL node, so both exact-inference
#      pipelines are exercised even under pytest -k filters,
#   6. a structure-recovery smoke: Chow-Liu learns a ground-truth tree from
#      sampled data, recovers it exactly, and the learned network answers a
#      schema-batched query through PGMQueryEngine,
#   7. the observability leg: one fresh process under REPRO_OBS=trace runs a
#      drifting stream_fit plus schema-batched PGMQueryEngine flushes, then
#      validate_obs_events checks the emitted JSONL against the event schema
#      and asserts the run produced ELBO-per-batch metrics, drift events,
#      per-bucket serve latency spans and kernel-dispatch counts; the obs
#      test module also re-runs once with REPRO_OBS=trace ambient so the
#      instrumentation is exercised at a non-default level under pytest,
#   7b. the temporal obs leg: a fresh process fits a dynamic HMM (fused),
#      replays a sequence stream through seq_stream_fit and serves
#      filter/predict queries via PGMQueryEngine mode="temporal", then
#      validate_obs_events asserts temporal_fit, stream_batch and
#      temporal_plan events all made it to the JSONL,
#   7c. the serving obs leg: a fresh process drives AsyncPGMServer through
#      timeout-triggered micro-batch flushes and a mid-stream hot model
#      swap, then validate_obs_events asserts serve_deadline, serve_swap,
#      the per-bucket serve_bucket telemetry and the aggregation-tier
#      slo / serve_health events all validate,
#   7c2. the replica-health demo leg: a fresh 2-replica AsyncPGMServer with
#      an injected slow_flush pinned to replica 0 — the health score must
#      diverge (replica 0 degraded, replica 1 not), dispatch must bias away
#      from the sick replica (strictly fewer buckets flushed by replica 0),
#      no ticket may be lost, and the run's Prometheus snapshot
#      (serve_request_ms histogram + replica_score gauges) and Chrome-trace
#      export must both render; the JSONL is then schema-validated for
#      serve_health + slo,
#   7d. the chaos leg: a fresh process under REPRO_OBS=trace runs the whole
#      fault-injection suite in one go — a NaN-poisoned fused stream replay
#      (held-posterior bit-identity asserted inline), a mid-stream
#      checkpoint + crash-recovery resume (bit-identity asserted inline),
#      and an AsyncPGMServer run through load shedding, one worker crash
#      and one transient plan-compile failure with zero lost tickets —
#      then validate_obs_events asserts the quarantine, checkpoint,
#      serve_shed, serve_retry and serve_worker events all validate.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

python -m pytest -x -q "$@"

# Kernel parity: the tier-1 run above already executes tests/test_kernels.py
# under the DEFAULT interpret policy (compiled on TPU runners, interpret on
# CPU); when that default resolves to COMPILED, force interpret mode once
# more so the two paths cannot silently diverge.  On runners whose default
# is already interpret (this CPU container, the GitHub runner) the forced
# leg would be byte-identical to the tier-1 run, so it is skipped.  If the
# tier-1 run was filtered via "$@", re-run the default-policy leg so the
# pair stays complete.
if [ "$#" -gt 0 ]; then
    python -m pytest -x -q tests/test_kernels.py
fi
DEFAULT_INTERPRET="$(python -c 'from repro.kernels import clg_stats; print(int(clg_stats._resolve_interpret(None)))')"
if [ "$DEFAULT_INTERPRET" = "0" ]; then
    echo "ci: kernel parity leg (default policy compiles — forcing interpret)"
    REPRO_PALLAS_INTERPRET=1 python -m pytest -x -q tests/test_kernels.py
else
    echo "ci: kernel parity leg skipped (default policy is already interpret)"
fi

BENCH_OUT="$(mktemp -t bench_streaming_smoke.XXXXXX.json)"
DVMP_OUT="$(mktemp -t bench_dvmp_smoke.XXXXXX.json)"
LATENT_OUT="$(mktemp -t bench_latent_smoke.XXXXXX.json)"
STRUCT_OUT="$(mktemp -t bench_structure_smoke.XXXXXX.json)"
TEMPORAL_OUT="$(mktemp -t bench_temporal_smoke.XXXXXX.json)"
SERVE_OUT="$(mktemp -t bench_serve_smoke.XXXXXX.json)"
RESIL_OUT="$(mktemp -t bench_resilience_smoke.XXXXXX.json)"
OBS_OUT="$(mktemp -t obs_events_smoke.XXXXXX.jsonl)"
OBS_TEMPORAL_OUT="$(mktemp -t obs_temporal_smoke.XXXXXX.jsonl)"
OBS_SERVE_OUT="$(mktemp -t obs_serve_smoke.XXXXXX.jsonl)"
OBS_HEALTH_OUT="$(mktemp -t obs_health_smoke.XXXXXX.jsonl)"
OBS_CHAOS_OUT="$(mktemp -t obs_chaos_smoke.XXXXXX.jsonl)"
trap 'rm -f "$BENCH_OUT" "$DVMP_OUT" "$LATENT_OUT" "$STRUCT_OUT" "$TEMPORAL_OUT" "$SERVE_OUT" "$RESIL_OUT" "$OBS_OUT" "$OBS_TEMPORAL_OUT" "$OBS_SERVE_OUT" "$OBS_HEALTH_OUT" "$OBS_CHAOS_OUT"' EXIT
python benchmarks/run.py --json --n 1000 --batch 250 --sweeps 2 \
    --window 2 --out "$BENCH_OUT"
python - "$BENCH_OUT" <<'EOF'
import json, sys
sys.path.insert(0, "benchmarks")
from run import validate_bench_streaming

with open(sys.argv[1]) as fh:
    payload = json.load(fh)
validate_bench_streaming(payload)
print("ci smoke: BENCH_streaming schema OK "
      f"(speedup {payload['speedup_inst_per_s']:.2f}x)")
EOF
python scripts/bench_compare.py --bench streaming \
    --fresh "$BENCH_OUT" --baseline BENCH_streaming.json

XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
python benchmarks/run.py --json --dvmp --n 2000 --sweeps 3 --out "$DVMP_OUT"
python - "$DVMP_OUT" <<'EOF'
import json, sys
sys.path.insert(0, "benchmarks")
from run import validate_bench_dvmp

with open(sys.argv[1]) as fh:
    payload = json.load(fh)
validate_bench_dvmp(payload)
print("ci smoke: BENCH_dvmp schema OK (mesh "
      f"{payload['config']['mesh_shape']}, posterior diff "
      f"{payload['posterior_max_abs_diff']:.2e})")
EOF

python benchmarks/run.py --json --latent --latent-n 512 --depth 6 \
    --out "$LATENT_OUT"
python - "$LATENT_OUT" <<'EOF'
import json, sys
sys.path.insert(0, "benchmarks")
from run import validate_bench_latent

with open(sys.argv[1]) as fh:
    payload = json.load(fh)
validate_bench_latent(payload)
print("ci smoke: BENCH_latent schema OK (kernel rel diff "
      f"{payload['latent_backend_max_rel_diff']:.2e}, strong-JT bucketed "
      f"{payload['jt_bucketed_speedup']:.2f}x, "
      f"diff {payload['jt_posterior_max_abs_diff']:.2e})")
EOF

python benchmarks/run.py --json --structure --structure-n 3000 \
    --structure-vars 6 --out "$STRUCT_OUT"
python - "$STRUCT_OUT" <<'EOF'
import json, sys
sys.path.insert(0, "benchmarks")
from run import validate_bench_structure

with open(sys.argv[1]) as fh:
    payload = json.load(fh)
validate_bench_structure(payload)
print("ci smoke: BENCH_structure schema OK (score diff "
      f"{payload['family_score_max_abs_diff']:.2e}, chowliu F1 "
      f"{payload['chowliu_edge_f1']:.2f}, hillclimb F1 "
      f"{payload['hillclimb_skeleton_f1']:.2f})")
EOF

python benchmarks/run.py --json --temporal --temporal-b 16 --temporal-t 8 \
    --sweeps 2 --out "$TEMPORAL_OUT"
python - "$TEMPORAL_OUT" <<'EOF'
import json, sys
sys.path.insert(0, "benchmarks")
from run import validate_bench_temporal

with open(sys.argv[1]) as fh:
    payload = json.load(fh)
validate_bench_temporal(payload)
print("ci smoke: BENCH_temporal schema OK (fused "
      f"{payload['speedup_seq_per_s']:.2f}x, posterior diff "
      f"{payload['fused_posterior_max_abs_diff']:.2e}, "
      f"retrace_free={payload['retrace_free']})")
EOF

XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
python benchmarks/run.py --json --serve --serve-duration 1.0 \
    --serve-loads 100 200 --out "$SERVE_OUT"
python - "$SERVE_OUT" <<'EOF'
import json, sys
sys.path.insert(0, "benchmarks")
from run import validate_bench_serve

with open(sys.argv[1]) as fh:
    payload = json.load(fh)
validate_bench_serve(payload)
single = [r for r in payload["results"] if r["driver"] == "serve_single"][0]
print("ci smoke: BENCH_serve schema OK "
      f"({single['achieved_qps']:.0f} q/s, p99 {single['p99_ms']:.1f}ms, "
      f"hit rate {payload['plan_cache_hit_rate']:.2f}, "
      f"zero_drop={payload['hot_swap_zero_drop']})")
EOF
python scripts/bench_compare.py --bench serve \
    --fresh "$SERVE_OUT" --baseline BENCH_serve.json

python benchmarks/run.py --json --resilience --n 4000 --batch 500 \
    --sweeps 2 --serve-duration 1.0 --out "$RESIL_OUT"
python - "$RESIL_OUT" <<'EOF'
import json, sys
sys.path.insert(0, "benchmarks")
from run import validate_bench_resilience

with open(sys.argv[1]) as fh:
    payload = json.load(fh)
validate_bench_resilience(payload)
s, f = payload["streaming"], payload["serving"]["faulted"]
print("ci smoke: BENCH_resilience schema OK "
      f"({s['quarantined']}/{s['n_batches']} batches quarantined, faulted "
      f"serve {f['achieved_qps']:.0f} q/s with {f['worker_restarts']} "
      f"restart(s), zero_loss={payload['serve_zero_loss']}, "
      f"resume_bit_identical={payload['resume_bit_identical']})")
EOF
python scripts/bench_compare.py --bench resilience \
    --fresh "$RESIL_OUT" --baseline BENCH_resilience.json

python - <<'EOF'
import jax.numpy as jnp
from repro.core.dag import BayesianNetwork, DAG, MultinomialCPD, Variables
from repro.infer_exact import JunctionTreeEngine

vs = Variables()
a = vs.new_multinomial("A", 2)
b = vs.new_multinomial("B", 2)
dag = DAG(vs)
dag.add_parent(b, a)
bn = BayesianNetwork(dag, {
    "A": MultinomialCPD(jnp.array([0.6, 0.4])),
    "B": MultinomialCPD(jnp.array([[0.9, 0.1], [0.2, 0.8]])),
})
eng = JunctionTreeEngine(bn)
eng.set_evidence({"B": 1})
eng.run_inference()
post = eng.posterior_discrete(a)
expect = jnp.array([0.6 * 0.1, 0.4 * 0.8])
expect = expect / expect.sum()
assert jnp.allclose(post, expect, atol=1e-6), (post, expect)
print(f"ci smoke: P(A | B=1) = {post} OK")
EOF

python - <<'EOF'
import numpy as np
import jax.numpy as jnp
from repro.core.dag import (BayesianNetwork, CLGCPD, DAG, MultinomialCPD,
                            Variables)
from repro.infer_exact import (JunctionTreeEngine, brute_posterior,
                               brute_posterior_mean_var)

# strong junction tree: Z -> X1 -> X2 -> X3 with X2 an unobserved
# continuous INTERNAL node (evidence on X1 and X3 only)
vs = Variables()
Z = vs.new_multinomial("Z", 2)
X1, X2, X3 = (vs.new_gaussian(n) for n in ("X1", "X2", "X3"))
dag = DAG(vs)
dag.add_parent(X1, Z)
dag.add_parent(X2, X1)
dag.add_parent(X3, X2)
bn = BayesianNetwork(dag, {
    "Z": MultinomialCPD(jnp.array([0.4, 0.6])),
    "X1": CLGCPD(jnp.array([0.0, 3.0]), jnp.zeros((2, 0)),
                 jnp.array([1.0, 0.5])),
    "X2": CLGCPD(jnp.asarray(1.0), jnp.asarray([0.8]), jnp.asarray(0.7)),
    "X3": CLGCPD(jnp.asarray(-0.5), jnp.asarray([1.2]), jnp.asarray(0.4)),
})
eng = JunctionTreeEngine(bn)
assert eng.strong
ev = {"X1": 0.9, "X3": 0.2}
eng.set_evidence(ev)
eng.run_inference()
pz = np.asarray(eng.posterior_discrete(Z))
assert np.allclose(pz, np.asarray(brute_posterior(bn, Z, ev)), atol=1e-5)
m, v = eng.posterior_mean_var(X2)
mb, vb = brute_posterior_mean_var(bn, X2, ev)
assert abs(float(m) - float(mb)) < 1e-5 and abs(float(v) - float(vb)) < 1e-5
print(f"ci smoke: strong JT P(Z | X1, X3) = {pz}, "
      f"E[X2 | e] = {float(m):.4f} OK")
EOF

python - <<'EOF'
import numpy as np
from repro.data import synthetic as syn
from repro.learn_structure import chow_liu, undirected_edges
from repro.serve.engine import PGMQueryEngine

# structure recovery: Chow-Liu must find a ground-truth tree exactly, and
# the learned network must serve schema-batched exact queries
bn = syn.random_discrete_bn(6, card=3, seed=3, tree=True)
stream = syn.bn_stream(bn, 4000, seed=4)
edges, learned = chow_liu(stream, stream.attributes)
true, got = undirected_edges(bn), undirected_edges(edges)
assert got == true, (sorted(map(tuple, true)), sorted(map(tuple, got)))
eng = PGMQueryEngine(learned, mode="exact")
qs = [eng.submit("D0", {"D2": k % 3, "D3": (k + 1) % 3}) for k in range(4)]
eng.flush()
for q in qs:
    assert q.done and abs(float(q.result.sum()) - 1.0) < 1e-5
print(f"ci smoke: Chow-Liu recovered the tree exactly "
      f"({len(edges)} edges), learned BN served {len(qs)} exact queries OK")
EOF

# obs leg: a FRESH process emits the full telemetry surface in one go,
# then the JSONL is schema-validated.
REPRO_OBS=trace REPRO_OBS_PATH="$OBS_OUT" python - <<'EOF'
import jax
import jax.numpy as jnp
from repro.core import streaming, vmp
from repro.core.dag import PlateSpec
from repro.data import synthetic as syn
from repro.serve.engine import PGMQueryEngine

# drifting stream -> stream_batch + drift events
stream, _ = syn.drift_stream(1000, 3, seed=8)
cp = vmp.compile_plate(PlateSpec(n_features=3, latent_card=1))
prior = vmp.default_prior(cp)
init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
batches = list(stream.batches(250))
state = streaming.stream_init(prior, init)
state, info = streaming.stream_fit(
    cp, prior, state,
    jnp.stack([b.xc for b in batches]), jnp.stack([b.xd for b in batches]),
    jnp.stack([b.mask for b in batches]), drift_threshold=3.0)
assert bool(info["drifted"].any()), "drift stream produced no drift event"

# schema-batched serving -> serve spans, bucket events, jt_plan
bn = syn.random_discrete_bn(5, card=3, seed=0, tree=True)
eng = PGMQueryEngine(bn, mode="exact")
for k in range(3):
    eng.submit("D0", {"D3": k % 3, "D4": (k + 1) % 3})
eng.submit("D0", {"D4": 1})
eng.flush()
for k in range(3):
    eng.submit("D0", {"D3": (k + 1) % 3, "D4": k % 3})   # cached schema
eng.flush()
EOF
python - "$OBS_OUT" <<'EOF'
import sys
from repro.obs import validate_obs_events

counts = validate_obs_events(sys.argv[1])
need = ("stream_batch", "drift", "span", "serve_flush", "serve_bucket",
        "jt_plan")
missing = [ev for ev in need if not counts.get(ev)]
assert not missing, f"obs leg missing event types: {missing} (got {counts})"
print(f"ci smoke: obs JSONL schema OK ({sum(counts.values())} events: "
      + ", ".join(f"{k}={counts[k]}" for k in sorted(counts)) + ")")
EOF

# temporal obs leg: fused dynamic-BN fit + sequence-batch streaming +
# temporal serving in a fresh process, validated against the event schema.
REPRO_OBS=basic REPRO_OBS_PATH="$OBS_TEMPORAL_OUT" python - <<'EOF'
import numpy as np
from repro.data import synthetic as syn
from repro.pgm_models import HiddenMarkovModel, seq_stream_fit
from repro.serve.engine import PGMQueryEngine

batches, attrs, switch_at = syn.hmm_stream(
    n_batches=4, s=16, t=10, states=2, f=2, shift=8.0, seed=0)
m = HiddenMarkovModel(attrs, n_states=2, seed=0)
m.update_model(batches[0], sweeps=3)              # temporal_fit event
info = seq_stream_fit(m, batches, sweeps=3, tol=0.0)   # stream_batch events
assert m.n_drifts >= 1, "temporal stream produced no drift event"
eng = PGMQueryEngine(m, mode="temporal")          # temporal_plan events
xc = np.asarray(batches[0].xc)
qs = [eng.submit("filter", {}, payload=xc[i]) for i in range(3)]
qs.append(eng.submit("predict", {"horizon": 2}, payload=xc[3]))
eng.flush()
assert all(q.done and np.isfinite(np.asarray(q.result)).all() for q in qs)
EOF
python - "$OBS_TEMPORAL_OUT" <<'EOF'
import sys
from repro.obs import validate_obs_events

counts = validate_obs_events(sys.argv[1])
need = ("temporal_fit", "stream_batch", "drift", "temporal_plan",
        "serve_bucket")
missing = [ev for ev in need if not counts.get(ev)]
assert not missing, f"temporal obs leg missing: {missing} (got {counts})"
print(f"ci smoke: temporal obs JSONL schema OK ("
      + ", ".join(f"{k}={counts[k]}" for k in sorted(counts)) + ")")
EOF

# serving obs leg: async micro-batching (timeout-triggered flushes) plus a
# mid-stream hot model swap in a fresh process; the swap and every flush
# decision must land in the JSONL and validate against the event schema.
REPRO_OBS=basic REPRO_OBS_PATH="$OBS_SERVE_OUT" python - <<'EOF'
import numpy as np
from repro.data import synthetic as syn
from repro.serve.queue import AsyncPGMServer

bn = syn.random_discrete_bn(5, card=2, max_parents=2, seed=0)
bn2 = syn.random_discrete_bn(5, card=2, max_parents=2, seed=1)
names = [v.name for v in bn.order]
server = AsyncPGMServer(bn, mode="exact", max_batch=64, max_delay_ms=20,
                        default_deadline_ms=60_000)
tickets = [server.submit(names[-1], {names[0]: float(k % 2)})
           for k in range(3)]
[t.result(timeout=120) for t in tickets]          # serve_deadline (timeout)
info = server.swap_model(bn2)                     # serve_swap
assert info["new_version"] == 1 and info["warmed_plans"] >= 1, info
tickets = [server.submit(names[-1], {names[0]: float(k % 2)})
           for k in range(3)]
out = [t.result(timeout=120) for t in tickets]    # served by the new network
server.stop()
assert server.stats()["pending"] == 0, server.stats()
assert all(np.isfinite(np.asarray(r)).all() for r in out)
EOF
python - "$OBS_SERVE_OUT" <<'EOF'
import sys
from repro.obs import validate_obs_events

counts = validate_obs_events(sys.argv[1])
need = ("serve_deadline", "serve_swap", "serve_bucket", "serve_flush",
        "slo", "serve_health")
missing = [ev for ev in need if not counts.get(ev)]
assert not missing, f"serve obs leg missing: {missing} (got {counts})"
print(f"ci smoke: serve obs JSONL schema OK ("
      + ", ".join(f"{k}={counts[k]}" for k in sorted(counts)) + ")")
EOF

# replica-health demo leg: one replica of a 2-replica server gets an
# injected slow_flush; the health score must diverge, dispatch must shift
# to the healthy replica, no ticket may be lost, and the run's Prometheus
# snapshot must render.
REPRO_OBS=trace REPRO_OBS_PATH="$OBS_HEALTH_OUT" python - <<'EOF'
import time

from repro.data import synthetic as syn
from repro.obs import default_prometheus_text
from repro.resilience import FaultInjector
from repro.serve.queue import AsyncPGMServer

bn = syn.random_discrete_bn(5, card=2, max_parents=2, seed=0)
names = [v.name for v in bn.order]


def q(i=0):
    return names[-1], {names[0]: float(i % 2)}


srv = AsyncPGMServer(bn, mode="exact", max_batch=8, max_delay_ms=5,
                     default_deadline_ms=60_000, replicas=2,
                     supervise_interval_ms=5)
srv.submit(*q()).result(timeout=120)                  # warm the plan
FaultInjector(seed=0).slow_flush(srv, delay_s=0.08, n=1000, widx=0)
tickets = []
deadline = time.monotonic() + 30.0
i = 0
while time.monotonic() < deadline:                    # degrade replica 0
    tickets.append(srv.submit(*q(i)))
    i += 1
    time.sleep(0.006)
    if srv.health.snapshots()[0]["degraded"]:
        break
assert srv.health.snapshots()[0]["degraded"], \
    "slow replica never marked degraded"
for j in range(30):                                   # biased dispatch phase
    tickets.append(srv.submit(*q(j)))
    time.sleep(0.006)
h = srv.health.snapshots()   # before stop(): the drain disables deferral
srv.stop()
st = srv.stats()
assert st["pending"] == 0, st                         # zero lost tickets
assert all(t.done() and t.error is None for t in tickets)
assert h[0]["degraded"] and not h[1]["degraded"], h
assert h[0]["score"] < 0.5 * h[1]["score"], h
assert h[0]["flushes"] < h[1]["flushes"], h           # dispatch shifted away

prom = default_prometheus_text()
assert "serve_request_ms_bucket" in prom and "replica_score" in prom
print(f"ci health demo: replica 0 score {h[0]['score']:.3f} "
      f"({h[0]['flushes']} flushes) vs replica 1 score {h[1]['score']:.3f} "
      f"({h[1]['flushes']} flushes), {len(tickets)} tickets all served, "
      f"prometheus {len(prom.splitlines())} lines")
EOF
python - "$OBS_HEALTH_OUT" <<'EOF'
import sys
from repro.obs import validate_obs_events

counts = validate_obs_events(sys.argv[1])
need = ("serve_health", "slo", "span")
missing = [ev for ev in need if not counts.get(ev)]
assert not missing, f"health demo leg missing: {missing} (got {counts})"
print(f"ci smoke: health obs JSONL schema OK ("
      + ", ".join(f"{k}={counts[k]}" for k in sorted(counts)) + ")")
EOF

# chaos leg: the fault-injection suite end to end in one fresh process —
# NaN quarantine (bit-identical to a never-poisoned replay), checkpoint +
# crash-recovery resume (bit-identical to the uninterrupted run), and a
# served workload through shedding, a worker crash and a transient compile
# failure with zero accepted tickets lost.
REPRO_OBS=trace REPRO_OBS_PATH="$OBS_CHAOS_OUT" python - <<'EOF'
import tempfile

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import streaming, vmp
from repro.core.dag import PlateSpec
from repro.data import synthetic as syn
from repro.resilience import (CheckpointManager, FaultInjector, ShedError,
                              resume_stream_fit)
from repro.serve.plan import PlanCache
from repro.serve.queue import AsyncPGMServer


def eq(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# NaN quarantine: poisoned replay == replay that never saw those batches
stream, _, _ = syn.gmm_stream(2000, 2, 3, seed=0)
cp = vmp.compile_plate(PlateSpec(n_features=3, latent_card=2))
prior = vmp.default_prior(cp)
init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
batches = list(stream.batches(250))
xcs = jnp.stack([b.xc for b in batches])
xds = jnp.stack([b.xd for b in batches])
inj = FaultInjector(seed=0)
bad, idx = inj.poison_nan(np.asarray(xcs), rate=0.15)
sp, _ = streaming.stream_fit(cp, prior, streaming.stream_init(prior, init),
                             jnp.asarray(bad), xds)       # quarantine events
keep = np.setdiff1d(np.arange(xcs.shape[0]), idx)
sc, _ = streaming.stream_fit(cp, prior, streaming.stream_init(prior, init),
                             xcs[keep], xds[keep])
assert int(sp.n_quarantined) == len(idx), (sp.n_quarantined, idx)
assert eq(sp.post, sc.post), "quarantined replay diverged"

# checkpoint + crash-recovery resume, bit-identical to the straight run
with tempfile.TemporaryDirectory() as ckdir:
    mgr = CheckpointManager(ckdir, every=0)
    head, _ = streaming.stream_fit(
        cp, prior, streaming.stream_init(prior, init), xcs[:4], xds[:4])
    mgr.save(4, head)                                     # checkpoint event
    resumed, _ = resume_stream_fit(
        cp, prior, streaming.stream_init(prior, init), xcs, xds, manager=mgr)
full, _ = streaming.stream_fit(cp, prior,
                               streaming.stream_init(prior, init), xcs, xds)
assert eq(resumed, full), "mid-stream resume diverged"

# serving chaos: bounded queue sheds, the drain crashes one worker (the
# supervisor respawns it and requeues the bucket) and the plan compile
# fails once transiently (retried) — every accepted ticket still resolves
bn = syn.random_discrete_bn(5, card=2, max_parents=2, seed=0)
names = [v.name for v in bn.order]
cache = PlanCache(compile_retries=2, retry_backoff_s=0.01)
inj.fail_compiles(cache, n=1)                             # serve_retry
srv = AsyncPGMServer(bn, mode="exact", max_batch=16, max_delay_ms=10_000,
                     default_deadline_ms=60_000, max_queue=2,
                     plan_cache=cache, supervise_interval_ms=5)
inj.crash_worker(srv)                                     # serve_worker
kept = [srv.submit(names[-1], {names[0]: float(k % 2)}) for k in range(2)]
shed = srv.submit(names[-1], {names[0]: 0.0})             # serve_shed
try:
    shed.result()
    raise SystemExit("over-max_queue submit was not shed")
except ShedError:
    pass
srv.stop()
st = srv.stats()
assert st["pending"] == 0, st                             # zero lost tickets
assert st["worker_restarts"] >= 1 and st["shed"] == 1, st
assert st["plans"]["retries"] >= 1, st
for t in kept:
    assert np.isfinite(np.asarray(t.result())).all()
print("ci chaos: quarantine bit-identical, resume bit-identical, "
      f"{st['worker_restarts']} worker restart(s), {st['shed']} shed, "
      f"{st['plans']['retries']} compile retry(s), zero lost tickets")
EOF
python - "$OBS_CHAOS_OUT" <<'EOF'
import sys
from repro.obs import validate_obs_events

counts = validate_obs_events(sys.argv[1])
need = ("quarantine", "checkpoint", "serve_shed", "serve_retry",
        "serve_worker")
missing = [ev for ev in need if not counts.get(ev)]
assert not missing, f"chaos obs leg missing: {missing} (got {counts})"
print(f"ci smoke: chaos obs JSONL schema OK ("
      + ", ".join(f"{k}={counts[k]}" for k in sorted(counts)) + ")")
EOF

echo "ci: obs-enabled pytest leg (REPRO_OBS=trace)"
OBS_PYTEST_OUT="$(mktemp -t obs_pytest.XXXXXX.jsonl)"
REPRO_OBS=trace REPRO_OBS_PATH="$OBS_PYTEST_OUT" \
    python -m pytest -x -q tests/test_obs.py
rm -f "$OBS_PYTEST_OUT"
