"""Prometheus exporter for the obs aggregation tier.

:func:`prometheus_text` — Prometheus text exposition (version 0.0.4) of a
:meth:`~repro.obs.agg.MetricsRegistry.snapshot`: counters and gauges as
single samples, histograms as cumulative ``_bucket{le=...}`` series plus
``_sum``/``_count``, ready to drop behind any scrape endpoint or push to a
textfile collector.  A pure read-side transform: it never touches the sink
or the registry hot paths, so it adds nothing to the ``REPRO_OBS=off``
cost.  (Spans need no exporter: they are profiler annotations, and a
``jax.profiler`` trace opened in Perfetto shows them beside the device
ops — ``obs/trace.py``.)
"""

from __future__ import annotations

from typing import Any, Dict, List


def _sanitize_name(name: str) -> str:
    out = [c if (c.isalnum() or c in "_:") else "_" for c in name]
    if out and out[0].isdigit():
        out.insert(0, "_")
    return "".join(out)


def _escape_label(v: Any) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace(
        "\n", "\\n")


def _labels_text(labels: Dict[str, Any], extra: str = "") -> str:
    parts = [f'{_sanitize_name(str(k))}="{_escape_label(v)}"'
             for k, v in sorted(labels.items())]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def _fmt(v: float) -> str:
    if v != v:
        return "NaN"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def prometheus_text(snapshot: Dict[str, Any]) -> str:
    """Render a registry snapshot in Prometheus text exposition format.

    Histogram buckets are emitted cumulatively with ``le`` set to the
    log-bucket upper edges (only buckets that change the cumulative
    count, plus ``+Inf``), matching how a Prometheus-native histogram
    with custom bounds would scrape.
    """
    lines: List[str] = []
    typed: set = set()

    def _type(name: str, kind: str) -> None:
        if name not in typed:
            typed.add(name)
            lines.append(f"# TYPE {name} {kind}")

    for e in snapshot["metrics"]:
        name = _sanitize_name(e["name"])
        kind = e["kind"]
        if kind in ("counter", "gauge"):
            _type(name, kind)
            lines.append(f"{name}{_labels_text(e['labels'])} {_fmt(e['value'])}")
            continue
        if kind != "histogram":
            raise ValueError(f"unknown metric kind {kind!r}")
        _type(name, "histogram")
        counts = {int(k): v for k, v in e["counts"].items()}
        cum = 0
        for b in sorted(counts):
            cum += counts[b]
            if b >= e["n_bins"]:
                continue            # overflow is covered by +Inf
            le = e["hi"] if b == e["n_bins"] - 1 else e["lo"] * e["growth"] ** (b + 1)
            lt = _labels_text(e["labels"], 'le="%r"' % le)
            lines.append(f"{name}_bucket{lt} {cum}")
        inf = _labels_text(e["labels"], 'le="+Inf"')
        lines.append(f"{name}_bucket{inf} {e['count']}")
        lines.append(f"{name}_sum{_labels_text(e['labels'])} {_fmt(e['sum'])}")
        lines.append(f"{name}_count{_labels_text(e['labels'])} {e['count']}")
    return "\n".join(lines) + "\n"

