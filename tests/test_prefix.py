"""ops/prefix.py: the blocked prefix sum and running maximum equal
jnp.cumsum and numpy's maximum.accumulate exactly."""

import jax
import numpy as np
import pytest

from tidb_tpu.ops import prefix


@pytest.mark.parametrize("dtype", [np.int64, np.int32], ids=["i64", "i32"])
@pytest.mark.parametrize("n", [1, 4096, 4097, 65536, 100_003, (1 << 20) + 1])
def test_cumsum_equals_numpy(n, dtype):
    rng = np.random.default_rng(n)
    x = rng.integers(-5, 1000, n).astype(dtype)
    got = np.asarray(jax.jit(prefix.cumsum)(x))
    assert got.dtype == dtype
    assert np.array_equal(got, np.cumsum(x, dtype=dtype))


def test_cumsum_wraps_like_numpy_on_overflow():
    x = np.full(10_000, np.iinfo(np.int64).max // 4096, dtype=np.int64)
    with np.errstate(over="ignore"):
        want = np.cumsum(x)
    assert np.array_equal(np.asarray(prefix.cumsum(jax.numpy.asarray(x))), want)


@pytest.mark.parametrize("dtype", [np.int64, np.int32], ids=["i64", "i32"])
@pytest.mark.parametrize("n", [1, 4096, 4097, 65536, 100_003, (1 << 20) + 1])
def test_cummax_equals_numpy(n, dtype):
    rng = np.random.default_rng(n)
    # mostly "nothing here" (-1) under rising marks, as the join's run
    # heads are, and all-negative stretches the zero padding must not win
    x = np.where(rng.random(n) < 0.2, np.arange(n) - n // 2, -n).astype(dtype)
    got = np.asarray(jax.jit(prefix.cummax)(x))
    assert got.dtype == dtype
    assert np.array_equal(got, np.maximum.accumulate(x))
