"""The benchmark's generator stays the formula users produce with.

``skybench/gen.py`` keeps its own copy of the reference producer's
distributions so that no program change can move the yardstick. This
test holds the copy to ``skyline_tpu/workload/generators.py``: the same
seed must give the same rows, so the stream the benchmark measures is the
stream ``workload/producer.py`` sends. Values up to 10,000 are exact in
the program's float32.
"""

import numpy as np
import pytest

from skybench import gen as bench_gen
from skyline_tpu.workload import generators

SEED = 7
N = 4096


@pytest.mark.parametrize("dims", [2, 3, 4, 8])
@pytest.mark.parametrize("distribution", ["uniform", "anti_correlated"])
def test_benchmark_generator_matches_program(distribution, dims):
    ours = getattr(bench_gen, distribution)(
        np.random.default_rng(SEED), N, dims, 0, 10000
    )
    theirs = getattr(generators, distribution)(
        np.random.default_rng(SEED), N, dims, 0, 10000
    )
    assert ours.shape == (N, dims)
    np.testing.assert_array_equal(
        np.asarray(ours, np.int64), theirs.astype(np.int64)
    )
