"""The benchmark's generators and schedules against the program's formats."""
from __future__ import annotations

import benchtest  # noqa: F401  (puts bench/ and src/ on the path)
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchkit.availability import class_bits, diurnal_dense, diurnal_pool
from benchkit.device import seed_key, sub_seed
from benchkit.loadgen import encode_bits
from benchkit.schedule import expand_tenants, open_arrivals

MIX = {"classes": [0.1, 0.3, 0.6, 0.9], "amplitude": 0.35, "period": 48, "rate_lo": 0.005, "rate_hi": 0.995}


def test_diurnal_rows_decode_through_the_program_to_the_dense_draws():
    from repro.kernels.unpack_bits import unpack_bits_ref

    K, rows = 1024, 6
    key = seed_key(2**33 + 7, 2)
    packed = diurnal_pool(key, K, rows, MIX)
    assert packed.shape == (rows, K // 8) and packed.dtype == jnp.uint8
    dense = diurnal_dense(key, K, rows, MIX)
    np.testing.assert_array_equal(np.asarray(unpack_bits_ref(packed, K)), dense)
    # the four classes keep their order of availability over a whole day
    per_class = dense.reshape(rows, 4, K // 4).mean(axis=(0, 2))
    assert 0.0 < dense.mean() < 1.0 and per_class[0] < per_class[-1]


def test_reference_unpack_matches_the_program():
    from benchtest import BENCH
    import importlib.util

    from repro.kernels.unpack_bits import unpack_bits_ref

    spec = importlib.util.spec_from_file_location("ref_round", BENCH / "reference" / "e3cs_round.py")
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    row = jax.random.bits(jax.random.PRNGKey(3), (96,), jnp.uint8)
    np.testing.assert_array_equal(np.asarray(ref.unpack_row(row, 768)), np.asarray(unpack_bits_ref(row, 768)))


def test_wire_bits_decode_through_the_protocol():
    from repro.serve.protocol import decode_bits

    bits = class_bits(np.random.default_rng(1), 1003, 3, MIX["classes"])
    for row in bits:
        np.testing.assert_array_equal(decode_bits(encode_bits(row).decode(), 1003), row.astype(np.float32))


@pytest.mark.parametrize("ties", [False, True])
def test_kth_largest_matches_a_sort(ties):
    from benchtest import BENCH
    import importlib.util

    spec = importlib.util.spec_from_file_location("ref_round", BENCH / "reference" / "e3cs_round.py")
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (5000,)))
    if ties:
        x = np.round(x, 1)  # many equal scores: the lower index wins, as in lax.top_k
    tau, mask = ref.kth_largest(jnp.asarray(x), 37)
    _, want = jax.lax.top_k(jnp.asarray(x), 37)
    assert float(tau) == float(np.sort(x)[-37])
    np.testing.assert_array_equal(np.nonzero(np.asarray(mask))[0], np.sort(np.asarray(want)))


def test_open_schedule_is_a_pure_function_of_the_seed():
    a = open_arrivals(2**40 + 3, 300.0, 1.1, 8, 5.0)
    b = open_arrivals(2**40 + 3, 300.0, 1.1, 8, 5.0)
    c = open_arrivals(2**40 + 4, 300.0, 1.1, 8, 5.0)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert any(not np.array_equal(x, y) for x, y in zip(a, c))
    # every seed offers the same ticks per tenant, Zipf-skewed, inside the window
    assert [len(x) for x in a] == [len(x) for x in c]
    assert len(a[0]) > 3 * len(a[-1]) and all(((x >= 0) & (x < 5.0)).all() for x in a)
    assert sum(len(x) for x in a) == pytest.approx(1500, abs=8)


def test_seeds_use_all_their_bits():
    assert sub_seed(5, 4, 0) != sub_seed(5 + 2**32, 4, 0) and 0 <= sub_seed(5, 4, 0) < 2**31
    assert not np.array_equal(np.asarray(seed_key(5, 1)), np.asarray(seed_key(5 + 2**32, 1)))


def test_tenant_groups_interleave():
    out = expand_tenants([{"count": 2, "K": 10}, {"count": 3, "K": 20}])
    assert [t["K"] for t in out] == [10, 20, 10, 20, 20] and [t["index"] for t in out] == list(range(5))
