"""Roofline and utilization arithmetic against counts made by hand."""

import pytest

from bench import run, trace, work
from bench.peaks import PEAKS, peaks_for

GMM = {"latent_card": 4, "continuous": 10, "discrete_cards": []}
NB = {"latent_card": 3, "continuous": 10, "discrete_cards": [4, 4]}
V5E = PEAKS["TPU v5 lite"]


def kernel_op(operands, start, dur):
    shapes = ", ".join(f"{dt}[{','.join(map(str, s))}]{{1,0}}"
                       for dt, s in operands)
    name = (f"%body.8 = f32[8,32]{{1,0}} custom-call(...), "
            f'custom_call_target="tpu_custom_call", '
            f"operand_layout_constraints={{{shapes}}}, "
            f"frontend_attributes={{kernel_metadata={{}}}}")
    return trace.Op(name, start, start + dur)


def fake_trace(ops, window_ns):
    return trace.Trace({"/device:TPU:0": ops}, [], (0.0, window_ns))


def ctx(cfg, tr):
    cell = run.Cell("c", run.ROOT, {}, {}, cfg, {}, {}, 0, 1.0, True)
    return run.ReadContext(cell, tr, {}, tr.window_s, V5E)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks_for("TPU v99")


def test_clg_gram_counts_by_hand():
    n = 1 << 20
    flops, byts = work.gram_clg(GMM, n)
    # y [10, n] and r [4, n] read once; 4 x 30 statistics written once
    assert byts == 4 * n * 14 + 4 * 120
    # r_k times (1, y_f, y_f^2) for 4 classes x 10 leaves, and y^2 per leaf
    assert flops == 2 * n * 4 * 30 + 10 * n
    assert work.least_seconds(flops, byts, V5E) == pytest.approx(
        (4 * n * 14 + 480) / 819e9)


def test_disc_gram_counts_by_hand():
    n = 1 << 18
    flops, byts = work.gram_disc(NB, n)
    assert byts == 4 * n * (2 + 3) + 4 * 3 * 8
    assert flops == 2 * n * 3 * 8


def test_estep_pass_counts_by_hand():
    n = 1000
    flops, byts = work.estep_pass(NB, n)
    assert byts == 4 * n * 12
    assert flops == (n * 3 * (60 + 10 + 2 + 5)
                     + work.gram_clg(NB, n)[0] + work.gram_disc(NB, n)[0])


def test_suffstats_roofline_from_launches():
    n = 1 << 20
    clg = [("f32", (10, n)), ("f32", (10, n)), ("f32", (4, n))]
    ops = [kernel_op(clg, 1000.0 + i * 1e6, 250_000.0) for i in range(3)]
    from bench.run import load_module

    reader = load_module(run.BENCH / "metrics" / "suffstats_roofline.py",
                         "m_roof")
    least = (4 * n * 14 + 480) / 819e9
    got = reader.read(ctx(GMM, fake_trace(ops, 10e6)))
    assert got == pytest.approx(100 * 3 * least / (3 * 250e-6))


def test_roofline_tells_the_two_kernels_apart():
    n = 1 << 18
    clg = [("f32", (10, n)), ("f32", (10, n)), ("f32", (3, n))]
    disc = [("s32", (2, n)), ("f32", (3, n))]
    ops = [kernel_op(clg, 0.0, 100_000.0), kernel_op(disc, 2e5, 50_000.0)]
    from bench.run import load_module

    reader = load_module(run.BENCH / "metrics" / "suffstats_roofline.py",
                         "m_roof2")
    least = (work.least_seconds(*work.gram_clg(NB, n), V5E)
             + work.least_seconds(*work.gram_disc(NB, n), V5E))
    got = reader.read(ctx(NB, fake_trace(ops, 1e6)))
    assert got == pytest.approx(100 * least / 150e-6)


def test_learn_mfu_counts_passes_by_clg_launches():
    n = 1 << 18
    clg = [("f32", (10, n)), ("f32", (10, n)), ("f32", (3, n))]
    disc = [("s32", (2, n)), ("f32", (3, n))]
    ops = []
    for i in range(5):
        ops += [kernel_op(clg, i * 1e6, 1e5), kernel_op(disc, i * 1e6 + 2e5,
                                                        1e5)]
    from bench.run import load_module

    reader = load_module(run.BENCH / "metrics" / "learn_mfu.py", "m_mfu")
    per_pass = max(work.estep_pass(NB, n)[0] / V5E.flops,
                   work.estep_pass(NB, n)[1] / V5E.hbm_bw)
    got = reader.read(ctx(NB, fake_trace(ops, 20e6)))
    assert got == pytest.approx(100 * 5 * per_pass / 20e-3)


def test_no_kernel_no_number():
    from bench.run import load_module

    for name in ("suffstats_roofline", "learn_mfu"):
        reader = load_module(run.BENCH / "metrics" / f"{name}.py", "m_" + name)
        assert reader.read(ctx(GMM, fake_trace([], 1e6))) is None
