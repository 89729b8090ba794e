"""Operations and bytes that the work needs, counted from shapes.

Each count is the least the work needs whatever implements it: every input
that the mathematics needs read once, every output written once, and the
arithmetic of the result itself.  A design column of ones, a padded row or
a recomputation is not work.  ``least_seconds`` turns a count into the
least time on a chip: the larger of operations over peak FLOP/s and bytes
over peak bandwidth.
"""

from __future__ import annotations

import re

F32 = 4


# entries per (continuous leaf, class) of the CLG statistics for the one
# design column of these configurations: sum r, sum r y and sum r y^2
STATS_PER_LEAF = 3


def gram_clg(cfg, n: int):
    """The CLG suff-stat reduction over ``n`` instances: the responsibility
    rows ``r [K, n]`` against the per-leaf products of ``y [F, n]``."""
    K, F = cfg["latent_card"], cfg["continuous"]
    flops = 2 * n * K * F * STATS_PER_LEAF + n * F      # + y^2
    byts = F32 * n * (F + K) + F32 * K * F * STATS_PER_LEAF
    return flops, byts


def gram_disc(cfg, n: int):
    """The discrete-count reduction: ``r [K, n]`` against the one-hot rows
    of ``xd [Fd, n]`` (int32)."""
    K, cards = cfg["latent_card"], cfg["discrete_cards"]
    flops = 2 * n * K * sum(cards)
    byts = F32 * n * (len(cards) + K) + F32 * K * sum(cards)
    return flops, byts


def estep_pass(cfg, n: int):
    """One local step (E-step and statistics) over ``n`` instances: read
    each instance once; per (leaf, class) the expected log-likelihood (6
    operations), per instance and class the sum over leaves, the discrete
    look-ups and a softmax (5), then the reductions."""
    K, F, cards = cfg["latent_card"], cfg["continuous"], cfg["discrete_cards"]
    flops = n * K * (6 * F + F + len(cards) + 5)
    byts = F32 * n * (F + len(cards))
    f1, _ = gram_clg(cfg, n)
    f2, _ = gram_disc(cfg, n) if cards else (0, 0)
    return flops + f1 + f2, byts


def least_seconds(flops: float, byts: float, peaks) -> float:
    return max(flops / peaks.flops, byts / peaks.hbm_bw)


_OPERANDS = "operand_layout_constraints={"
_SHAPE = re.compile(r"([a-z]+[0-9]+)\[([0-9,]*)\]")


def kernel_operands(op_name: str):
    """``[(dtype, shape), ...]`` of a ``tpu_custom_call`` from its HLO text
    in the trace (``operand_layout_constraints={f32[10,n]{1,0}, ...}``), or
    None for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in op_name:
        return None
    start = op_name.find(_OPERANDS)
    if start < 0:
        return None
    start += len(_OPERANDS)
    depth, end = 1, start
    while depth and end < len(op_name):
        depth += {"{": 1, "}": -1}.get(op_name[end], 0)
        end += 1
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(op_name[start:end - 1])]


def gram_kind(operands) -> str:
    """``"disc"`` for the discrete-count Gram (an int32 operand), ``"clg"``
    for the CLG Gram (float operands only)."""
    return "disc" if any(dt.startswith("s") for dt, _ in operands) else "clg"
