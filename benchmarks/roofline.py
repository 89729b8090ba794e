"""Roofline analysis — hardware peaks, achieved-vs-peak scoring, and the
dry-run artifact report.

    compute_s    = HLO_FLOPs_per_device / peak FLOP/s      (bf16 MXU peak)
    memory_s     = HLO_bytes_per_device / HBM BW
    collective_s = link_bytes_per_device / link BW         (ICI per link)

FLOPs/bytes come from the trip-count-aware HLO analyzer (hlo_analysis.py)
over the post-SPMD module (xla's cost_analysis undercounts scan bodies).
Link-byte model: all-reduce costs 2x its payload (reduce-scatter +
all-gather halves of a ring), the others 1x.

The hardware peaks come from :data:`PEAKS`, a table keyed by the
``device_kind`` jax reports, each row with its published source.  A device
kind missing from the table is an error (:func:`peaks_for`), never a
default: a roofline drawn against the wrong chip's peaks is a wrong number.

:func:`achieved_vs_peak` is the live half (ROADMAP Pallas item):
``benchmarks/run.py`` registers it as the ``achieved_vs_peak`` obs
estimator, so the PGM kernel bench blocks (``--latent``, ``--structure``)
stamp measured-throughput-vs-roof fractions (and the compute/memory
bound classification) next to each row, from the analytical FLOP/byte
counts of the very program they timed — on a chip in :data:`PEAKS` only.

MODEL_FLOPS = 6*N*D (train) or 2*N*D (inference) with N = active params;
the ratio MODEL_FLOPS / HLO_FLOPs exposes remat/attention/padding overhead.

Usage: PYTHONPATH=src python -m benchmarks.roofline \
           [--dryrun results/dryrun] [--hlo results/hlo] [--mesh 16x16] \
           [--device-kind "TPU v5 lite"] [--chips 256]
Writes results/roofline.csv and results/roofline.md.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import sys
from typing import Optional


@dataclasses.dataclass(frozen=True)
class HardwarePeaks:
    """Peak rates of the accelerator the roofline is drawn against."""

    flops: float                # bf16 MXU peak, FLOP/s per chip
    hbm_bw: float               # HBM bandwidth, B/s per chip
    link_bw: float              # ICI per-link bandwidth, B/s
    source: str


# Keyed by ``jax.devices()[i].device_kind``.
PEAKS = {
    "TPU v5 lite": HardwarePeaks(
        flops=197e12, hbm_bw=819e9,
        # 1,600 Gbit/s of chip-to-chip interconnect over 4 ICI links
        link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e' system architecture"),
}


def peaks_for(device_kind: str) -> HardwarePeaks:
    """The published peaks of ``device_kind``; raises on a kind not in
    :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def achieved_vs_peak(*, seconds: float, flops: Optional[float] = None,
                     hbm_bytes: Optional[float] = None,
                     peaks: Optional[HardwarePeaks] = None) -> dict:
    """Score a measured region against the hardware roof.

    ``flops`` / ``hbm_bytes`` are the work done in ``seconds`` (per
    device); ``peaks`` default to those of the device jax runs on (an
    unknown device kind raises).  Returns achieved FLOP/s and B/s, their
    fractions of peak, and which roof the region sits under (``bound``:
    the resource whose peak-fraction is higher is the one limiting further
    speedup).
    Registered as the ``achieved_vs_peak`` obs estimator by
    ``benchmarks/run.py``.
    """
    if peaks is None:
        import jax

        peaks = peaks_for(jax.devices()[0].device_kind)
    out: dict = {"seconds": seconds,
                 "peak_flops": peaks.flops, "peak_hbm_bw": peaks.hbm_bw}
    frac_f = frac_b = None
    if flops is not None and seconds > 0:
        out["achieved_flops_per_s"] = flops / seconds
        frac_f = out["frac_peak_flops"] = flops / seconds / peaks.flops
    if hbm_bytes is not None and seconds > 0:
        out["achieved_bytes_per_s"] = hbm_bytes / seconds
        frac_b = out["frac_peak_hbm_bw"] = hbm_bytes / seconds / peaks.hbm_bw
    if frac_f is not None and frac_b is not None:
        out["bound"] = "compute" if frac_f >= frac_b else "memory"
    return out


def model_flops_per_device(rec: dict, chips: int) -> float:
    """6*N_active*D (train) / 2*N_active*D (inference), per chip."""
    from repro.configs.base import INPUT_SHAPES

    shape = INPUT_SHAPES[rec["shape"]]
    n = rec["n_active"]
    if rec["kind"] == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n * tokens
    elif rec["kind"] == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n * tokens
    else:  # decode: ONE token per stream
        total = 2.0 * n * shape.global_batch
    return total / chips


def analyze_record(rec: dict, hlo_dir: str, peaks: HardwarePeaks,
                   chips: int) -> dict:
    from benchmarks.hlo_analysis import analyze

    tag = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}"
    path = os.path.join(hlo_dir, tag + ".hlo.txt")
    with open(path) as f:
        h = analyze(f.read())
    link_bytes = (2 * h["coll_all-reduce"] + h["coll_all-gather"]
                  + h["coll_reduce-scatter"] + h["coll_all-to-all"]
                  + h["coll_collective-permute"])
    compute_s = h["flops"] / peaks.flops
    # bytes: [min, max] — min assumes TPU-grade fusion (only matmul/conv/
    # collective/slice traffic hits HBM), max is the unfused CPU-HLO bound.
    memory_s_min = h["hbm_bytes_min"] / peaks.hbm_bw
    memory_s = h["hbm_bytes"] / peaks.hbm_bw
    coll_s = link_bytes / peaks.link_bw
    # dominance judged on the fused (TPU-realistic) memory bound
    terms = {"compute": compute_s, "memory": memory_s_min,
             "collective": coll_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_device(rec, chips)
    rec = dict(rec)
    rec.update({
        "hlo_flops": h["flops"], "hlo_bytes": h["hbm_bytes"],
        "hlo_bytes_min": h["hbm_bytes_min"],
        "link_bytes": link_bytes,
        "compute_s": compute_s, "memory_s": memory_s,
        "memory_s_min": memory_s_min,
        "collective_s": coll_s, "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": mf / max(h["flops"], 1.0),
        "coll_detail": {k: h[f"coll_{k}"] for k in
                        ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute")},
    })
    rec["note"] = _note(rec)
    return rec


def _note(r: dict) -> str:
    """One sentence: what would move the dominant term down."""
    if r["dominant"] == "memory":
        if r["kind"] == "decode":
            return ("decode is weight/KV-read bound: quantize weights or "
                    "batch more streams per chip to amortize reads")
        return ("fp32 activation traffic dominates: fuse residual chains / "
                "bf16 the saved remat activations")
    if r["dominant"] == "collective":
        return ("all-reduce bound: overlap grad reduce-scatter with bwd "
                "compute or shift sharding from TP toward FSDP")
    if r["useful_ratio"] < 0.5:
        return ("compute-bound with low useful ratio: cut remat recompute "
                "or attention waste (flash kernel)")
    return "compute-bound near the MXU roof: increase per-chip batch"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="results/dryrun")
    ap.add_argument("--hlo", default="results/hlo")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--out", default="results/roofline")
    ap.add_argument("--device-kind", default="TPU v5 lite",
                    help="device_kind whose peaks (PEAKS) draw the roof; "
                         "the dry-run meshes are v5e pods")
    ap.add_argument("--chips", type=int, default=256,
                    help="pod size for the per-device model-FLOP split")
    args = ap.parse_args(argv)
    peaks = peaks_for(args.device_kind)

    recs = []
    for path in sorted(glob.glob(os.path.join(args.dryrun, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("mesh") != args.mesh:
            continue
        if "skipped" in rec or "error" in rec:
            recs.append(rec)
            continue
        try:
            recs.append(analyze_record(rec, args.hlo, peaks, args.chips))
        except FileNotFoundError:
            rec["note"] = "no HLO dump"
            recs.append(rec)

    # ---- csv ----
    cols = ["arch", "shape", "kind", "dominant", "compute_s",
            "memory_s_min", "memory_s", "collective_s", "hlo_flops",
            "hlo_bytes_min", "hlo_bytes", "link_bytes", "model_flops",
            "useful_ratio"]
    with open(args.out + ".csv", "w") as f:
        f.write(",".join(cols) + ",note\n")
        for r in recs:
            if "skipped" in r:
                f.write(f"{r['arch']},{r['shape']},skip,,,,,,,,,,"
                        f"\"{r['skipped']}\"\n")
                continue
            f.write(",".join(str(r.get(c, "")) for c in cols)
                    + f",\"{r.get('note', '')}\"\n")

    # ---- markdown ----
    with open(args.out + ".md", "w") as f:
        f.write("| arch | shape | compute_s | memory_s (fused..unfused) |"
                " collective_s | dominant | MODEL/HLO flops | note |\n"
                "|---|---|---|---|---|---|---|---|\n")
        for r in recs:
            if "skipped" in r:
                f.write(f"| {r['arch']} | {r['shape']} | — | — | — | skip |"
                        f" — | {r['skipped'][:60]} |\n")
                continue
            f.write(
                f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3g} |"
                f" {r['memory_s_min']:.3g}..{r['memory_s']:.3g} |"
                f" {r['collective_s']:.3g} |"
                f" **{r['dominant']}** | {r['useful_ratio']:.2f} |"
                f" {r['note'][:80]} |\n")
    print(f"[roofline] wrote {args.out}.csv / .md ({len(recs)} rows)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
