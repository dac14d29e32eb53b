"""The threshold select (``repro.kernels.radix_select``) against ``lax.top_k``.

The round's cohort mask comes from a counting radix select on the score
bits; it must equal ``selection_mask(lax.top_k(s, k)[1], K)`` bit for bit —
ties broken lowest index first, ``-0.0`` below ``+0.0``, ``-inf`` entries
(inactive clients of a padded shard) at the bottom — and its ``ties`` flag
must fire exactly when the ``k``-th score ties one left out.  The round tests
pin where the select runs: on its route (a TPU; forced here) the staged
E3CS round, dense and on a 1-device mesh, gives the masks of the
``lax.top_k`` route bit for bit, holds no top-k sort, and every device op of
the select sits under ``round.sample``; the paths that keep ``lax.top_k``
still have it.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig
from repro.core.selection.sampling import perturbed_scores, plackett_luce_sample, selection_mask
from repro.engine import round_program
from repro.engine.round_program import RoundProgram
from repro.kernels import radix_select
from repro.kernels.radix_select import count_ge_kernel_call, count_ge_ref, score_keys, topk_mask
from repro.obs.taps import ROUND_TAPS

_mask = jax.jit(topk_mask, static_argnames=("k",))


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 16 rows (2048 keys), so a few thousand keys span several
    grid steps of the count kernel."""
    monkeypatch.setattr(radix_select, "ROWS", 16)


def _scores(kind: str, K: int, seed: int) -> jnp.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "normal":
        s = rng.normal(size=K)
    elif kind == "ties":  # a handful of values: the k-th score is nearly always tied
        s = rng.integers(0, 4, K)
    elif kind == "zeros":  # signed zeros next to +-1
        s = rng.choice(np.array([0.0, -0.0, 1.0, -1.0]), K)
    else:  # half the population inactive, as the D=1 shard path masks it
        s = np.where(rng.random(K) < 0.5, -np.inf, rng.normal(size=K))
    return jnp.asarray(np.asarray(s, np.float32))


def _expected(s: jnp.ndarray, k: int):
    """lax.top_k's mask, and whether its k-th key ties a key left out."""
    mask = selection_mask(jax.lax.top_k(s, k)[1], s.shape[0])
    keys = np.sort(np.asarray(score_keys(s)))[::-1]
    return np.asarray(mask), bool(k < keys.size and keys[k - 1] == keys[k])


CASES = [(K, k) for K in (1, 7, 1000, 2049, 5000) for k in sorted({1, max(K // 3, 1), K})]


@pytest.mark.parametrize("kind", ["normal", "ties", "zeros", "neg_inf"])
@pytest.mark.parametrize("K,k", CASES, ids=[f"K{K}-k{k}" for K, k in CASES])
def test_mask_equals_top_k(K, k, kind):
    s = _scores(kind, K, seed=K + k)
    want, want_ties = _expected(s, k)
    mask, ties = _mask(s, k=k)
    assert np.asarray(mask).dtype == np.float32
    np.testing.assert_array_equal(np.asarray(mask).view(np.int32), want.view(np.int32))
    assert bool(ties) == want_ties


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_every_radix_width_agrees(monkeypatch, bits):
    # RADIX_BITS is read at trace time: a jit of its own for each width
    monkeypatch.setattr(radix_select, "RADIX_BITS", bits)
    s = _scores("ties", 3000, seed=bits)
    want, want_ties = _expected(s, 100)
    mask, ties = jax.jit(lambda s: topk_mask(s, 100))(s)
    np.testing.assert_array_equal(np.asarray(mask), want)
    assert bool(ties) == want_ties


def test_signed_zero_order_is_top_k_order():
    # lax.top_k orders +0.0 above -0.0 (the floats' total order), so a
    # +0.0 beats an earlier -0.0; the keys order them the same way
    s = jnp.asarray(np.array([-0.0, 0.0, -0.0, 0.0, -1.0], np.float32))
    np.testing.assert_array_equal(np.asarray(jax.lax.top_k(s, 2)[1]), [1, 3])
    mask, ties = _mask(s, k=2)
    np.testing.assert_array_equal(np.asarray(mask), [0, 1, 0, 1, 0])
    assert not bool(ties)
    mask, ties = _mask(s, k=3)  # one of two -0.0: the lower index
    np.testing.assert_array_equal(np.asarray(mask), [1, 1, 0, 1, 0])
    assert bool(ties)
    keys = np.asarray(score_keys(jnp.asarray(np.array([-np.inf, -1.0, -0.0, 0.0, 1.0, np.inf], np.float32))))
    assert np.all(np.diff(keys) > 0)


def test_planted_ties_lowest_index_first():
    # the k-th score is shared by clients 10, 20, 30 and 40; two slots left
    s = np.full(64, -5.0, np.float32)
    s[[3, 50]] = 9.0
    s[[40, 10, 30, 20]] = 1.0
    mask, ties = _mask(jnp.asarray(s), k=4)
    assert bool(ties)
    np.testing.assert_array_equal(np.flatnonzero(np.asarray(mask)), [3, 10, 20, 50])


def test_k_out_of_range_raises():
    with pytest.raises(ValueError, match="k <= K"):
        topk_mask(jnp.zeros(8), 9)
    with pytest.raises(ValueError, match="0 < k"):
        topk_mask(jnp.zeros(8), 0)


@pytest.mark.parametrize("K", [1000, 4096, 6000, 6145])
def test_count_kernel_matches_reference(K):
    # interpret mode: one or several grid steps, whole or with a partial last
    # block (masked in the kernel), probes at both ends of the key range
    keys = score_keys(_scores("zeros" if K == 1000 else "normal", K, seed=K))
    thr = jnp.asarray(np.r_[np.iinfo(np.int32).min + 1, -5, 0, 1, 1 << 30, np.iinfo(np.int32).max], jnp.int32)
    got = count_ge_kernel_call(keys, thr, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(count_ge_ref(keys, thr)))


def test_select_through_the_kernel(monkeypatch):
    # the whole select with the Pallas count (interpret mode) in the loop
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    s = _scores("ties", 5000, seed=11)
    want, want_ties = _expected(s, 300)
    mask, ties = topk_mask(s, 300)
    np.testing.assert_array_equal(np.asarray(mask), want)
    assert bool(ties) == want_ties


# ---------------------------------------------------------------------------
# Where the select runs: the compiled programs
# ---------------------------------------------------------------------------

K_HLO, k_HLO = 5000, 52
_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+)\s+([\w\-]+)\(.*?op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{$")


def _device_ops(text: str):
    """``(name, shape, opcode, op_name)`` of the instructions a device runs:
    those outside fusion bodies (a trace names an op by its top-level
    instruction, and the benchmark reads its stage from that op_name)."""
    fused = set(re.findall(r"kind=k\w+, calls=%?([\w.\-]+)", text))
    ops, inside = [], None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside = head.group(1)
            continue
        m = _OP.match(line)
        if m and inside not in fused:
            ops.append(m.groups())
    return ops


def _topk_sorts(text: str):
    """Top-k instructions: an XLA sort, or the CPU backend's ``TopK`` custom
    call (the staged e3cs round with the bisect allocator sorts nothing else)."""
    return [line for line in text.splitlines() if " sort(" in line or 'custom_call_target="TopK"' in line]


@pytest.fixture
def threshold_route(monkeypatch):
    """Build rounds on the threshold select's route, as on a TPU (the count
    itself stays on its CPU reference)."""
    monkeypatch.setattr(round_program, "threshold_select_route", lambda: True)


def _staged_program(mesh=None, **fl_kw):
    fl = FLConfig(K=K_HLO, k=k_HLO, rounds=4, scheme="e3cs", allocator="bisect", quota_frac=0.5,
                  volatility="bernoulli", seed=0, **fl_kw)
    return RoundProgram.from_config(fl, mesh=mesh, override="packed", block=4)


def _compiled_round(program) -> str:
    run, s0 = program.build_runner(outputs="lean", carry_key=True, taps=True, scan_length=4)
    width = -(-K_HLO // 8) if program.mesh is None else program._sharded_geometry()[2]
    xs = jnp.zeros((4, width), jnp.uint8)
    return run.lower(s0, jax.random.PRNGKey(0), ROUND_TAPS.init_counters(), xs).compile().as_text()


@pytest.mark.parametrize("async_", [False, True], ids=["sync", "async"])
@pytest.mark.parametrize("placement", ["local", "mesh1"])
def test_round_masks_equal_top_k_route(monkeypatch, placement, async_):
    from repro.launch.mesh import make_host_mesh

    fl = FLConfig(K=3000, k=100, rounds=12, scheme="e3cs", allocator="bisect", quota_frac=0.5,
                  volatility="bernoulli", seed=2, staleness_rounds=2 if async_ else 0)

    def run(threshold):
        monkeypatch.setattr(round_program, "threshold_select_route", lambda: threshold)
        mesh = make_host_mesh(1) if placement == "mesh1" else None
        program = RoundProgram.from_config(fl, mesh=mesh)
        run, s0 = program.build_runner(outputs="full", taps=True)
        state, masks, *_, taps = run(s0, jax.random.PRNGKey(5), jnp.zeros((12, 0), jnp.float32))
        return np.asarray(masks), np.asarray(state.e3cs.logw), np.asarray(taps["series"]["topk_ties"])

    masks, logw, ties = run(True)
    masks_top_k, logw_top_k, ties_top_k = run(False)
    np.testing.assert_array_equal(masks, masks_top_k)
    np.testing.assert_array_equal(logw.view(np.int32), logw_top_k.view(np.int32))
    assert masks.sum(1).tolist() == [100.0] * 12
    assert not ties_top_k.any()  # the lax.top_k route reports no ties


@pytest.mark.parametrize("placement", ["local", "mesh1"])
def test_staged_round_has_no_top_k_sort(threshold_route, placement):
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(1) if placement == "mesh1" else None
    text = _compiled_round(_staged_program(mesh))
    assert not _topk_sorts(text)
    # the select's device ops (scoped ``radix_select``) all sit under round.sample
    ops = _device_ops(text)
    select_ops = [op for op in ops if "radix_select" in op[3]]
    assert select_ops
    assert all("round.select/round.sample/" in op[3] for op in select_ops)
    # every device op over the int32 keys is one of them
    key_ops = [op for op in ops if op[1].startswith(f"s32[{K_HLO}]") and op[2] not in (
        "get-tuple-element", "parameter", "tuple", "bitcast")]
    assert key_ops
    assert all("round.sample" in op[3] for op in key_ops), key_ops


def test_top_k_paths_keep_top_k():
    # the FL training round's sampler emits ids in score order
    p = jnp.full((K_HLO,), k_HLO / K_HLO, jnp.float32)
    text = jax.jit(lambda key: plackett_luce_sample(key, p, k_HLO)).lower(jax.random.PRNGKey(0)).compile().as_text()
    assert _topk_sorts(text)
    # its ids are exactly the mask the threshold select writes
    key = jax.random.PRNGKey(7)
    mask, _ = topk_mask(perturbed_scores(key, p), k_HLO)
    np.testing.assert_array_equal(
        np.asarray(selection_mask(plackett_luce_sample(key, p, k_HLO), K_HLO)), np.asarray(mask)
    )


@pytest.mark.skipif(jax.device_count() < 2, reason="needs 2 host devices")
def test_sharded_d2_keeps_candidate_merge(threshold_route):
    from repro.launch.mesh import make_host_mesh

    program = _staged_program(make_host_mesh(2))
    text = _compiled_round(program)
    assert _topk_sorts(text)
    assert not [op for op in _device_ops(text) if "radix_select" in op[3]]
