"""The comparisons that decide ``correct``.

Learning cells are compared like training: the first calls of the window's
own entry point, made during set-up, against the reference following the
same calls.  Three numbers, each the worst case:

- ``elbo_gap``: per checked call, ``|ELBO - ELBO_ref| / |ELBO_ref|``;
- ``first_update_gap``: the first call's update, the posterior minus the
  prior in natural coordinates (the expected statistics the conjugate
  update added);
- ``change3_gap``: the change of the posterior over the checked calls, in
  natural coordinates.

Both updates are taken leaf by leaf: the gap between the program's norm and
the reference's, over the larger of the reference's norm of that leaf and
of the median leaf.  Leaves whose first update in the reference is under a
thousandth of the median leaf's (nought to rounding, such as the discrete
tables of a model without discrete leaves) are left out.

Serving cells compare every answer with the reference's ``q(Z | x)`` of
the same row in log space: ``answer_log_gap`` is the largest
``|ln p - ln p_ref|`` over rows and classes, each probability floored at
``1e-30``.  Well-separated classes put nearly every probability at 0 or 1,
where a difference of probabilities shows nothing; their logarithms still
carry every log-likelihood term, so a lower precision shows there.
"""

from __future__ import annotations

import numpy as np

LEAVES = ("mix", "kk", "km", "a", "bq", "disc")


def _norms(delta):
    return {k: float(np.linalg.norm(np.asarray(delta[k], np.float64)))
            for k in LEAVES}


def _diff(a, b):
    return {k: np.asarray(a[k], np.float64) - np.asarray(b[k], np.float64)
            for k in LEAVES}


def update_gap(prog_delta, ref_delta, live) -> float:
    p, r = _norms(prog_delta), _norms(ref_delta)
    med = float(np.median([r[k] for k in live]))
    return max(abs(p[k] - r[k]) / max(r[k], med) for k in live)


def live_leaves(ref_first_update) -> list:
    r = _norms(ref_first_update)
    med = float(np.median(list(r.values())))
    return [k for k in LEAVES if r[k] >= 1e-3 * med]


def learn_readings(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``prior``, ``init`` and ``posts`` (natural
    coordinates, one per checked call) and ``elbos``."""
    ref_first = _diff(ref["posts"][0], ref["prior"])
    live = live_leaves(ref_first)
    elbo = max(abs(p - r) / max(abs(r), 1.0)
               for p, r in zip(prog["elbos"], ref["elbos"]))
    return {
        "elbo_gap": float(elbo),
        "first_update_gap": update_gap(
            _diff(prog["posts"][0], prog["prior"]), ref_first, live),
        "change3_gap": update_gap(
            _diff(prog["posts"][-1], prog["init"]),
            _diff(ref["posts"][-1], ref["init"]), live),
    }


FLOOR = 1e-30


def log_gaps(answers: np.ndarray, ref_answers: np.ndarray) -> np.ndarray:
    """Per row, the largest ``|ln p - ln p_ref|`` over the classes."""
    def ln(p):
        return np.log(np.maximum(np.asarray(p, np.float64), FLOOR))

    return np.abs(ln(answers) - ln(ref_answers)).max(-1)


def serve_readings(answers: np.ndarray, ref_answers: np.ndarray) -> dict:
    return {"answer_log_gap": float(log_gaps(answers, ref_answers).max())}


def judge(readings: dict, limits: dict) -> list:
    """``[(name, value, limit)]`` for every number compared; a number with
    no limit is an error, never a pass."""
    missing = sorted(set(readings) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing}")
    return [(k, readings[k], float(limits[k])) for k in sorted(readings)]
