"""Cell sizes a CPU test run can hold: same traffic kinds, small batches."""

TINY = {
    "gmm_large.stream": {"pool_batches": 4, "batch": 4096},
    "nb_mixed.drift": {"pool_batches": 24, "batch": 2048},
    "gmm_large.serve": {"pool": 4096, "rate_qps": 200, "warm_max_pow2": 6},
}
SEED = 2 ** 31 + 12345       # larger than 32 signed bits hold, as the driver's
