"""Training driver: ``python -m repro.launch.train --arch <id> [options]``.

On this CPU container it runs REDUCED configs end-to-end (the e2e example
uses a ~100M-param model); on a real pod the same driver takes the full
config + production mesh.
"""

from __future__ import annotations

import argparse
import time
from functools import partial

import numpy as np

from repro.launch.cache import use_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", choices=["adamw", "vb"], default="adamw")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="full config (requires a real pod)")
    ap.add_argument("--data-shards", type=int, default=1)
    ap.add_argument("--model-shards", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--corpus-size", type=int, default=200_000)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.configs import get_config
    from repro.data.tokens import TokenStream, markov_sequence_fast
    from repro.launch.mesh import make_host_mesh
    from repro.nn import transformer as T
    from repro.train import checkpoint as ck
    from repro.train import optimizer as opt
    from repro.train import step as ts
    from repro.bayes.drift import LossDriftMonitor

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    obs.log(f"[train] arch={cfg.name} params~{cfg.n_params()/1e6:.1f}M "
            f"optimizer={args.optimizer}",
            component="train", arch=cfg.name, n_params=cfg.n_params(),
            optimizer=args.optimizer)

    sh = T.NO_SHARD
    if args.data_shards * args.model_shards > 1:
        mesh = make_host_mesh(args.data_shards, args.model_shards)
        sh = T.Shardings(mesh=mesh, data_axes=("data",), model_axis="model")

    key = jax.random.PRNGKey(args.seed)
    ep = args.model_shards if cfg.moe else 1
    params = T.init_model(key, cfg, ep_shards=ep)

    corpus = markov_sequence_fast(args.corpus_size, cfg.vocab, seed=args.seed)
    enc_stub = ((cfg.encoder.enc_len, cfg.d_model) if cfg.is_encdec else None)
    stream = TokenStream(corpus, args.batch, args.seq, enc_stub=enc_stub)

    lr_fn = opt.cosine_schedule(args.lr, args.steps // 10, args.steps)
    monitor = LossDriftMonitor.create()

    if args.optimizer == "adamw":
        state = ts.init_train_state(params)
        jstep = jax.jit(partial(ts.train_step, cfg=cfg, sh=sh, lr_fn=lr_fn))
    else:
        state = ts.init_vb_state(params)
        jstep = jax.jit(partial(ts.vb_train_step, cfg=cfg, sh=sh,
                                n_total=float(args.corpus_size)))

    t0 = time.time()
    losses = []
    for i, batch in enumerate(stream.batches(args.steps)):
        state, metrics = jstep(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        monitor, drifted = monitor.observe(jnp.asarray(loss))
        if i % args.log_every == 0 or i == args.steps - 1:
            tps = args.batch * args.seq * (i + 1) / (time.time() - t0)
            obs.log(f"[train] step={i:5d} loss={loss:.4f} tok/s={tps:,.0f}"
                    + (" DRIFT" if bool(drifted) else ""),
                    component="train", step=i, loss=loss, tok_s=tps,
                    drifted=bool(drifted))
    obs.log(f"[train] done: first={losses[0]:.3f} last={losses[-1]:.3f} "
            f"log(V)={np.log(cfg.vocab):.3f}",
            component="train", first_loss=losses[0], last_loss=losses[-1])
    if args.ckpt:
        p = state.params if args.optimizer == "adamw" else state.vb.mean
        ck.save(args.ckpt, p)
        obs.log(f"[train] checkpoint -> {args.ckpt}", component="train",
                ckpt=args.ckpt)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
