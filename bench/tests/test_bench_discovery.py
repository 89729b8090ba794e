"""A configuration, a traffic mix and a per-layer metric added as new files
and entries run with no edit to any file already there."""

import json
import shutil

from bench import run
from bench.tests.tiny import SEED


def test_new_cell_and_metric_by_files_alone(tmp_path):
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}

    cfg = json.loads((run.BENCH / "configs" / "gmm_large.json").read_text())
    cfg.update(name="gmm_small", continuous=6, latent_card=3)
    (tmp_path / "bench" / "configs" / "gmm_small.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "tiny_stream.json").write_text(
        json.dumps({"kind": "learn_closed", "feed": "array",
                    "pool_batches": 3, "batch": 2048, "call_batches": 1,
                    "sweeps": 50, "tol": 1e-5, "checked_calls": 3}))
    (tmp_path / "bench" / "limits" / "gmm_small.tiny_stream.json").write_text(
        json.dumps({"elbo_gap": 1e-4, "first_update_gap": 1e-4,
                    "change3_gap": 1e-4}))
    (tmp_path / "bench" / "metrics" / "calls_per_s.py").write_text(
        "def read(ctx):\n"
        "    return ctx.counters['calls'] / ctx.window_s\n")
    bench["configs"].append({"name": "gmm_small", "source": "test",
                             "file": "bench/configs/gmm_small.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "gmm_small.tiny_stream",
                               "config": "gmm_small",
                               "traffic": "tiny_stream", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "learn_inst_per_s" == m["name"] or "update_ms_p95" == m["name"]:
            m["workloads"].append("gmm_small.tiny_stream")
    bench["per_layer"].append({"name": "calls_per_s", "unit": "calls/s",
                               "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": "learn_inst_per_s",
                               "workloads": ["gmm_small.tiny_stream"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    plain = run.run_cell("gmm_small.tiny_stream", SEED, 1.0, False,
                         root=tmp_path, require_tpu=False)
    assert plain["correct"], plain["checks"]
    assert set(plain["metrics"]) == {"learn_inst_per_s", "update_ms_p95",
                                     "setup_s"}
    traced = run.run_cell("gmm_small.tiny_stream", SEED, 1.0, True,
                          root=tmp_path, require_tpu=False)
    assert traced["metrics"]["calls_per_s"]["value"] > 0
    assert "breakdown" in traced and "window_s" in traced["device"]
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    assert all(after[k] == v for k, v in before.items())


def test_no_tpu_no_result(capsys):
    assert run.main(["--workload", "gmm_large.stream", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 3
    assert capsys.readouterr().out == ""
