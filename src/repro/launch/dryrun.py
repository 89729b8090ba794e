"""Multi-pod dry-run: prove every (arch x shape x mesh) lowers + compiles.

MUST be the first import side effect: 512 placeholder host devices for the
production mesh (before ANY jax-touching import).
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402
import argparse
import json
import re
import sys
import time
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

# obs is deliberately jax-free (safe even before the XLA_FLAGS line above)
from repro import obs
from repro.configs import ARCH_IDS, get_config
from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import data_axes_of, make_production_mesh
from repro.nn import transformer as T
from repro.sharding import decode_state_specs, param_specs, train_state_specs
from repro.train import optimizer as opt
from repro.train import step as ts

# ---------------------------------------------------------------------------
# skip table (DESIGN.md §decode coverage): long_500k needs sub-quadratic attn
# ---------------------------------------------------------------------------


def skip_reason(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("full-attention arch: 524k dense KV decode is the quadratic "
                "regime this shape excludes (DESIGN.md)")
    if shape.name == "long_500k" and cfg.is_encdec:
        return "enc-dec audio arch: 30s/1500-frame context by construction"
    return None


# ---------------------------------------------------------------------------
# abstract inputs (ShapeDtypeStruct — no allocation)
# ---------------------------------------------------------------------------


def _axis_sizes(mesh):
    return {a: mesh.shape[a] for a in mesh.axis_names}


def _sds(shape, dtype, mesh, spec):
    from repro.sharding.specs import fix_spec

    spec = fix_spec(spec, tuple(shape), _axis_sizes(mesh))
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _with_sharding(tree, spec_tree, mesh):
    return jax.tree_util.tree_map(
        lambda leaf, spec: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)),
        tree, spec_tree)


TRAIN_SHARDING = os.environ.get("REPRO_TRAIN_SHARDING", "tp_fsdp")


def shardings_for(cfg: ModelConfig, mesh, mode: str) -> T.Shardings:
    dp = data_axes_of(mesh)
    model_size = mesh.shape["model"]
    if mode == "train" and TRAIN_SHARDING == "fsdp":
        # pure FSDP (§Perf change C): every axis is a batch axis
        all_axes = tuple(mesh.axis_names)
        from repro.configs.base import INPUT_SHAPES  # batch divisibility
        return T.Shardings(mesh=mesh, data_axes=all_axes, model_axis="model",
                           shard_heads=False, moe_ep=False)
    seq_shard = bool(cfg.n_heads) and (cfg.n_heads % model_size != 0)
    if mode == "decode":
        # q/o stay head-sharded so the ctx-parallel shard_map boundary
        # gathers the TINY q activation, not the attention weights
        # (§Perf change D); small-head archs fall back to replication.
        return T.Shardings(mesh=mesh, data_axes=dp, model_axis="model",
                           shard_heads=not seq_shard, attn_seq_shard=False)
    return T.Shardings(
        mesh=mesh, data_axes=dp, model_axis="model",
        shard_heads=True, attn_seq_shard=seq_shard)


def abstract_params(cfg: ModelConfig, mesh, mode: str, dtype):
    fsdp = mode == "train" and TRAIN_SHARDING == "fsdp"
    ep = 1 if fsdp else (mesh.shape["model"] if cfg.moe else 1)
    shape_tree = jax.eval_shape(
        lambda: T.init_model(jax.random.PRNGKey(0), cfg, ep_shards=ep,
                             dtype=dtype))
    specs = param_specs(shape_tree, cfg,
                        "train_fsdp" if fsdp else mode,
                        data_axes=data_axes_of(mesh), model_axis="model",
                        axis_sizes=_axis_sizes(mesh))
    return _with_sharding(shape_tree, specs, mesh), specs


def input_specs(arch: str, shape_name: str, mesh, mode_override=None
                ) -> Tuple[str, tuple, Any]:
    """Returns (kind, args-as-ShapeDtypeStructs, step callable)."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    dp = data_axes_of(mesh)
    kind = mode_override or shape.kind
    B, S = shape.global_batch, shape.seq_len

    if kind == "train":
        sh = shardings_for(cfg, mesh, "train")
        params_sds, pspecs = abstract_params(cfg, mesh, "train", jnp.float32)
        state_shape = jax.eval_shape(
            lambda p: ts.init_train_state(p), params_sds)
        fsdp = TRAIN_SHARDING == "fsdp"
        sspecs = train_state_specs(
            state_shape, cfg, data_axes=dp, axis_sizes=_axis_sizes(mesh),
            mode="train_fsdp" if fsdp else "train")
        state_sds = _with_sharding(state_shape, sspecs, mesh)
        bdp = sh.data_axes if fsdp else dp
        batch_sds = ts.TrainBatch(
            tokens=_sds((B, S), jnp.int32, mesh, P(bdp, None)),
            labels=_sds((B, S), jnp.int32, mesh, P(bdp, None)),
            enc_input=(_sds((B, cfg.encoder.enc_len, cfg.d_model),
                            jnp.float32, mesh, P(dp, None, None))
                       if cfg.is_encdec else None),
        )
        lr_fn = opt.cosine_schedule(3e-4, 100, 10_000)

        def fn(state, batch):
            return ts.train_step(state, batch, cfg, sh, lr_fn=lr_fn)

        return kind, (state_sds, batch_sds), fn

    if kind == "prefill":
        sh = shardings_for(cfg, mesh, "prefill")
        params_sds, _ = abstract_params(cfg, mesh, "serve", jnp.bfloat16)
        toks = _sds((B, S), jnp.int32, mesh, P(dp, None))
        enc = (_sds((B, cfg.encoder.enc_len, cfg.d_model), jnp.bfloat16,
                    mesh, P(dp, None, None)) if cfg.is_encdec else None)

        def fn(params, tokens, enc_input):
            out = T.forward(params, tokens, cfg, sh, remat=False,
                            enc_input=enc_input)
            # serving prefill emits next-token logits (KV-write bytes are
            # accounted analytically in §Roofline notes)
            return out.logits[:, -1]

        return kind, (params_sds, toks, enc), fn

    # decode
    sh = shardings_for(cfg, mesh, "decode")
    params_sds, _ = abstract_params(cfg, mesh, "decode", jnp.bfloat16)
    capacity = S
    if cfg.sliding_window and shape.name == "long_500k":
        capacity = cfg.sliding_window       # ring buffer IS the window
    # cache capacity must divide the model axis for ctx-parallel sharding
    ms = mesh.shape["model"]
    capacity = max(ms, (capacity // ms) * ms)
    state_shape = jax.eval_shape(
        lambda p: T.init_decode_state(
            p, cfg, B, capacity, T.NO_SHARD,
            enc_input=(jnp.zeros((B, cfg.encoder.enc_len, cfg.d_model),
                                 jnp.bfloat16) if cfg.is_encdec else None)),
        params_sds)
    dspecs = decode_state_specs(state_shape, cfg, data_axes=dp,
                                axis_sizes=_axis_sizes(mesh))
    state_sds = jax.tree_util.tree_map(
        lambda leaf, spec: None if leaf is None else jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)),
        state_shape, dspecs,
        is_leaf=lambda x: x is None)
    tok = _sds((B, 1), jnp.int32, mesh, P(dp, None))

    def fn(params, state, token):
        return ts.serve_step(params, state, token, cfg, sh)

    return kind, (params_sds, state_sds, tok), fn


# ---------------------------------------------------------------------------
# collective-bytes extraction from post-SPMD HLO
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
                "u32": 4, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f8e4m3": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(f64|s64|u64|c64|f32|s32|u32|bf16|f16|s16|u16|s8|u8|"
                       r"pred|f8e4m3|f8e5m2)\[([0-9,]*)\]")

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(segment):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes of every collective op, by kind.

    Uses each op's RESULT shape (the payload that crosses/lands on links);
    bytes are whole-module (all devices); §Roofline divides by chips x link.
    """
    out = {k: 0 for k in _COLL_KINDS}
    out["count"] = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        # result-defining collective lines look like: %x = TYPE[...] all-reduce(
        m = re.search(r"=\s*([^=]*?)\s+(all-gather|all-reduce|reduce-scatter|"
                      r"all-to-all|collective-permute)", ls)
        if not m:
            continue
        kind = m.group(2)
        out[kind] += _shape_bytes(m.group(1))
        out["count"] += 1
    return out


# ---------------------------------------------------------------------------
# the dry run
# ---------------------------------------------------------------------------


def run_one(arch: str, shape_name: str, multi_pod: bool,
            save_hlo: Optional[str] = None) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "n_params": cfg.n_params(), "n_active": cfg.n_active_params(),
    }
    if reason:
        rec["skipped"] = reason
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    kind, args, fn = input_specs(arch, shape_name, mesh)
    with mesh:
        lowered = jax.jit(fn).lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    rec.update({
        "kind": kind,
        "lower_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
        },
        "collectives": coll,
    })
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help=f"one of {ARCH_IDS} or 'all'")
    ap.add_argument("--shape", default="all",
                    help=f"one of {list(INPUT_SHAPES)} or 'all'")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--hlo-dir", default=None,
                    help="also dump post-SPMD HLO text here")
    args = ap.parse_args(argv)
    use_compile_cache()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    if args.hlo_dir:
        os.makedirs(args.hlo_dir, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                out_path = os.path.join(args.out, tag + ".json")
                hlo_path = (os.path.join(args.hlo_dir, tag + ".hlo.txt")
                            if args.hlo_dir else None)
                try:
                    rec = run_one(arch, shape, mp, save_hlo=hlo_path)
                    status = ("SKIP: " + rec["skipped"][:40]
                              if "skipped" in rec else
                              f"ok lower={rec['lower_s']}s "
                              f"compile={rec['compile_s']}s "
                              f"flops={rec['flops']:.3g}")
                except Exception as e:  # noqa: BLE001 — report and continue
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "error": f"{type(e).__name__}: {e}"}
                    status = "FAIL " + rec["error"][:120]
                    failures += 1
                with open(out_path, "w") as f:
                    json.dump(rec, f, indent=1)
                obs.log(f"[dryrun] {tag}: {status}", component="dryrun",
                        tag=tag, status=status)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
