#!/usr/bin/env bash
# CI runner (run by .github/workflows/ci.yml on every push and pull request,
# and by hand via `bash scripts/ci.sh [pytest args]`).  Two steps:
#   1. the tier-1 pytest suite with -x (a collection error, such as a jax
#      import that moved between versions, fails fast instead of landing);
#      every check CI makes is a test of this suite,
#   2. kernel interpret-vs-policy parity: tests/test_kernels.py runs once
#      more with REPRO_PALLAS_INTERPRET=1 forced when the default policy
#      compiles, so on a TPU runner the compiled Mosaic path is checked
#      against the same oracles the CPU verifies in interpret mode.  It is
#      the only step that acts differently on a TPU runner.
# Speed is measured on the chip by the benchmark (BENCHMARK.json, bench/),
# never here.
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
