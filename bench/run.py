#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell's configuration (``bench/configs/<config>.json``, whose
``reference`` names a plain reference module beside it), its traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` names the driver in
``bench/drivers/``), the limits of its output check
(``bench/limits/<workload>.json``) and each per-layer metric's reader
(``bench/metrics/<metric>.py``).  A new cell or metric is new files and
entries only.

The driver makes its inputs from ``--seed``, warms up (set-up), measures
for ``--seconds`` inside the harness's window, and hands back its numbers
and the check of its output, which runs after the window against the plain
reference.  With ``--trace 1`` the window runs under the JAX profiler and
the per-layer metrics are reported instead of the end-to-end ones.

Earlier lines (standard error) say what is worth knowing: peak device
memory, compiles inside the window, sweeps, generator lateness.  The last
line of standard output is one JSON object; the last lines of standard
error are the compared numbers beside their limits.  Without a TPU, or
with fewer chips than the cell needs, the run prints no result and exits 3.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    """The machine has no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    root: Path
    bench: dict
    entry: dict
    cfg: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    devices: list = dataclasses.field(default_factory=list)

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    def reference(self):
        """The configuration's plain reference module."""
        return load_module(
            self.root / "bench" / "configs" / f"{self.cfg['reference']}.py",
            f"bench_ref_{self.cfg['reference']}")

    def log(self, msg: str) -> None:
        log(msg)


def load_cell(workload: str, seed: int, seconds: float, trace: bool,
              root: Path = ROOT,
              traffic_overrides: Optional[dict] = None) -> Cell:
    bench = read_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    entry = entries[workload]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = read_json(root / cfgs[entry["config"]]["file"])
    traffic = read_json(root / "bench" / "traffic" / f"{entry['traffic']}.json")
    traffic.update(traffic_overrides or {})
    limits = read_json(root / "bench" / "limits" / f"{workload}.json")
    return Cell(workload, root, bench, entry, cfg, traffic, limits, seed,
                seconds, trace)


# -- the measured window ----------------------------------------------------


class Window:
    """The driver's measured window: set-up ends where it opens.

    Counts the programs built (compiled, or read from the persistent
    cache) and the traces made while it is open, runs the profiler over
    it when the cell is traced, and reads the peak device memory when it
    closes."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    RETRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self, cell: Cell):
        self.cell = cell
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.compiles = 0
        self.retraces = 0
        self.memory_peak: List[int] = []
        self.trace_dir: Optional[str] = None
        self._open = False
        self._annotation = None

    def _listen(self, event: str, _secs: float, **_kw) -> None:
        if self._open:
            if event == self.COMPILE:
                self.compiles += 1
            elif event == self.RETRACE:
                self.retraces += 1

    def __enter__(self) -> "Window":
        import jax

        self.setup_s = time.monotonic() - T_PROCESS
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        if self.cell.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=profile_options())
        self._annotation = jax.profiler.TraceAnnotation("bench.window")
        self._annotation.__enter__()
        self._open = True
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        import jax

        self.window_s = time.monotonic() - self._t0
        self._open = False
        self._annotation.__exit__(None, None, None)
        if self.cell.trace:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(self._listen)
        self.memory_peak = [int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in self.cell.devices]


def profile_options():
    """Device ops and the ``bench.*`` spans, without the Python tracer,
    which would slow every Python call of the host path it measures."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


@dataclasses.dataclass
class Outcome:
    """What a driver hands back once its window has closed and the
    program's state is freed."""

    e2e: Dict[str, float]                 # end-to-end metric -> value
    attempted: int
    failed: int
    check: Callable[[], Dict[str, float]]  # readings, run after the window
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)


def span(name: str):
    """A host span on the profiler's clock, named ``bench.<name>``."""
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{name}")


# -- metrics ----------------------------------------------------------------


def cell_metrics(cell: Cell, kind: str) -> List[dict]:
    """The metrics of ``kind`` (``end_to_end`` or ``per_layer``) this cell
    reports."""
    e2e = [m for m in cell.bench["end_to_end"]
           if cell.name in m.get("workloads", [cell.name])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in cell.bench["per_layer"]
            if (cell.name in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


@dataclasses.dataclass
class ReadContext:
    """What a per-layer metric's reader is given."""

    cell: Cell
    trace: Any                  # bench.trace.Trace of the traced window
    counters: Dict[str, Any]    # counts the driver took in the window
    window_s: float
    peaks: Any                  # bench.peaks.Peaks of this device kind


def read_layer_metrics(cell: Cell, ctx: ReadContext) -> Dict[str, dict]:
    out = {}
    for m in cell_metrics(cell, "per_layer"):
        path = cell.root / "bench" / "metrics" / f"{m['name']}.py"
        reader = load_module(path, "bench_metric_" + m["name"].replace(
            ".", "_").replace("-", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- one run ----------------------------------------------------------------


def devices_for(cell: Cell, require_tpu: bool) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:cell.chips]


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (the path is part of the cache key), for every program however small."""
    import jax

    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_tpu: bool = True,
             traffic_overrides: Optional[dict] = None) -> dict:
    """Run one cell and return its result line (a dict); raises
    :class:`NoChip` before any work when the chips are missing."""
    cell = load_cell(workload, seed, seconds, trace, root, traffic_overrides)
    cell.devices = devices_for(cell, require_tpu)
    if require_tpu:
        log(f"compile cache: {use_compile_cache(root)}")
    d0 = cell.devices[0]
    log(f"cell {cell.name}: platform {d0.platform}, device_kind "
        f"{d0.device_kind!r}, {len(cell.devices)} chip(s), seed {seed}, "
        f"{seconds} s")
    from bench.peaks import peaks_for

    peaks = peaks_for(d0.device_kind) if require_tpu else None
    driver = load_module(
        root / "bench" / "drivers" / f"{cell.traffic['kind']}.py",
        f"bench_driver_{cell.traffic['kind']}")
    window = Window(cell)
    outcome: Outcome = driver.run(cell, window)
    for d, p in zip(cell.devices, window.memory_peak):
        log(f"peak_bytes_in_use device {d.id}: {p}")
    log(f"compiles inside the window: {window.compiles} "
        f"(retraces {window.retraces})")
    t0 = time.monotonic()
    from bench.compare import judge

    checks = judge(outcome.check(), cell.limits)
    log(f"output check: {time.monotonic() - t0:.1f} s")
    correct = all(v <= lim for _, v, lim in checks) and outcome.failed == 0
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(cell.devices),
              "memory_peak_bytes": max(window.memory_peak, default=0)}
    result: Dict[str, Any] = {"correct": correct,
                              "attempted": outcome.attempted,
                              "failed": outcome.failed}
    if trace:
        from bench import trace as tr

        t0 = time.monotonic()
        trc = tr.load(tr.find_xplane(window.trace_dir))
        shutil.rmtree(window.trace_dir, ignore_errors=True)
        ctx = ReadContext(cell, trc, outcome.counters, trc.window_s, peaks)
        result["metrics"] = read_layer_metrics(cell, ctx)
        device["busy_s"] = tr.busy_s(trc)
        device["window_s"] = trc.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(trc),
                               "idle_gaps": tr.idle_gaps(trc)}
        log(f"trace read in {time.monotonic() - t0:.1f} s")
    else:
        units = {m["name"]: m["unit"] for m in cell_metrics(cell,
                                                            "end_to_end")}
        values = dict(outcome.e2e, setup_s=window.setup_s)
        result["metrics"] = {k: {"value": float(values[k]), "unit": u}
                             for k, u in units.items()}
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, v, lim in checks}
    if outcome.failed:
        result["checks"]["failed"] = {"value": outcome.failed, "limit": 0}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        log(str(e))
        return 3
    for k, c in result["checks"].items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
