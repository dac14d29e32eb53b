"""The main-path Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
dynamic indexing, rank-1 blocks that are not whole lane tiles, shape casts
between lane layouts, vector loop carries.  These tests lower and compile
each main-path kernel at K=10,000,000 and tile 8192 for a described,
unattached ``v5e:2x2`` chip — the TPU compiler runs here, nothing executes —
and check that the kernel is really in the compiled program; the fused
K-sharded round is compiled whole for the four chips of the mesh, and the
benchmark cells' staged rounds for one chip (no sort in either).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so a worker that merely imports
this file must not touch it.
"""
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.bisect_tiles import bisect_block_sums_kernel_call
from repro.kernels.gumbel_topk import gumbel_topk_kernel_call
from repro.kernels.radix_select import count_ge_kernel_call
from repro.kernels.round_fused import fused_select_kernel_call, round_tail_kernel_call
from repro.kernels.unpack_bits import unpack_bits_kernel_call, unpack_crumbs_kernel_call

K, k, TILE = 10_000_000, 1000, 8192


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe the chip skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("from_w", [True, False], ids=["from_w", "from_p"])
def test_fused_select(one_chip, from_w):
    vec = _spec(one_chip, (K,))
    if from_w:
        def fn(w, g, s):
            return fused_select_kernel_call(
                w, g, k, scalars=(s[0], s[1], s[2], s[3] > 0), sigma=s[4], tile=TILE
            )

        _compile(fn, vec, vec, _spec(one_chip, (5,)))
    else:
        _compile(lambda p, g: fused_select_kernel_call(p, g, k, tile=TILE), vec, vec)


def test_gumbel_topk(one_chip):
    _compile(lambda s: gumbel_topk_kernel_call(s, k, tile=TILE), _spec(one_chip, (K,)))


TAIL_CASES = [("bits", 0), ("crumbs", 2), ("x", 0), ("lag", 2)]


@pytest.mark.parametrize("kind,S", TAIL_CASES, ids=[f"{kind}-S{S}" for kind, S in TAIL_CASES])
def test_round_tail(one_chip, kind, S):
    obs = {
        "bits": _spec(one_chip, ((K + 7) // 8,), jnp.uint8),
        "crumbs": _spec(one_chip, ((K + 3) // 4,), jnp.uint8),
        "x": _spec(one_chip, (K,)),
        "lag": _spec(one_chip, (K,), jnp.int32),
    }[kind]
    vec = _spec(one_chip, (K,))
    rings = (_spec(one_chip, (S, K)),) * (2 if S else 0)  # credit + late-feedback rings

    def fn(obs, mask, p, capped, logw, loss, residual, *rings):
        return round_tail_kernel_call(
            obs, mask, p, capped, logw, loss, *rings, kind=kind, residual=residual,
            eta=0.5, K_glob=K, decay=tuple(0.5 ** (s + 1) for s in range(S)), tile=TILE,
        )

    _compile(fn, obs, vec, vec, vec, vec, vec, _spec(one_chip, ()), *rings)


@pytest.mark.parametrize("n_caps", [4, 15], ids=["4caps", "block4-15caps"])
def test_bisect_block_sums(one_chip, n_caps):
    _compile(
        lambda w, caps: bisect_block_sums_kernel_call(w, caps, tile=TILE),
        _spec(one_chip, (K,)), _spec(one_chip, (n_caps,)),
    )


@pytest.mark.parametrize("n,n_thr", [(K, 3), (K, 15), (1_000_000, 3), (1000, 3)],
                         ids=["1e7-3probes", "1e7-15probes", "1e6-3probes", "1e3-3probes"])
def test_count_ge(one_chip, n, n_thr):
    # K=1e7 and 1e6 end in a partial block; 1000 keys are less than one tile
    _compile(
        count_ge_kernel_call,
        _spec(one_chip, (n,), jnp.int32), _spec(one_chip, (n_thr,), jnp.int32),
    )


@pytest.mark.parametrize("unpack,cpb", [(unpack_bits_kernel_call, 8), (unpack_crumbs_kernel_call, 4)],
                         ids=["bits", "crumbs"])
def test_unpack(one_chip, unpack, cpb):
    _compile(lambda b: unpack(b, K), _spec(one_chip, (-(-K // cpb),), jnp.uint8))


@pytest.mark.parametrize("sync", [True, False], ids=["sync", "async"])
def test_sharded_fused_round(topo, monkeypatch, sync):
    """The whole fused round of the K-sharded engine on the 2x2 mesh (packed
    replay, 20 rounds): the kernels and the ``(D*k,)`` top-k candidate
    exchange are in the compiled program."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.configs.base import FLConfig
    from repro.engine.round_program import RoundProgram

    # the program asks the default backend for its kernel route; take a TPU's
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices[:4]), ("shards",))
    K_mesh, T = 4 * 102_400, 20
    fl = FLConfig(K=K_mesh, k=k, rounds=T, scheme="e3cs", allocator="bisect", quota_frac=0.5,
                  volatility="diurnal", seed=0, staleness_rounds=0 if sync else 2)
    program = RoundProgram.from_config(
        fl, mesh=mesh, override="packed" if sync else "packed_lags",
        feedback="deadline" if sync else "late_credit", block=4, fused=True,
    )
    run, state0 = program.build_runner(outputs="lean", taps=True)
    replicated = NamedSharding(mesh, PartitionSpec())
    state = jax.tree.map(lambda a: _spec(replicated, np.shape(a), a.dtype), state0)
    xs = _spec(replicated, (T, K_mesh // (8 if sync else 4)), jnp.uint8)
    text = run.lower(state, _spec(replicated, (2,), jnp.uint32), xs).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"\[{4 * k}\]\S* (?:all-gather|all-reduce)", text)


@pytest.mark.parametrize("cell", ["fleet", "coord"])
def test_staged_round_sorts_nothing(topo, monkeypatch, cell):
    """The benchmark cells' staged rounds on one chip: the fleet's dense
    round (K=1e7, packed replay) and the coord's ``ShardedEngine(D=1)``
    round (K=1e6 on a 1-device mesh, dense rows).  The select stage is the
    threshold select's kernel, and the program holds no sort (``lax.top_k``
    lowers to one over K)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.configs.base import FLConfig
    from repro.engine.round_program import RoundProgram
    from repro.obs.taps import ROUND_TAPS

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices[:1]), ("shards",))
    on_chip = NamedSharding(mesh, PartitionSpec())
    K_cell = 10_000_000 if cell == "fleet" else 1_000_000
    fl = FLConfig(K=K_cell, k=520, rounds=10**9, scheme="e3cs", allocator="bisect", quota_frac=0.5)
    if cell == "fleet":
        program = RoundProgram.from_config(fl, override="packed", block=4)
        run, state0 = program.build_runner(outputs="lean", carry_key=True, taps=True, scan_length=2)
        rest = (jax.tree.map(lambda a: _spec(on_chip, (), a.dtype), ROUND_TAPS.init_counters()),
                _spec(on_chip, (2, K_cell // 8), jnp.uint8))
    else:
        program = RoundProgram.from_config(fl, mesh=mesh, override="dense", block=4)
        run, state0 = program.build_runner(outputs="full", carry_key=True, scan_length=1)
        rest = (_spec(on_chip, (1, K_cell)),)
    state = jax.tree.map(lambda a: _spec(on_chip, np.shape(a), a.dtype), state0)
    text = run.lower(state, _spec(on_chip, (2,), jnp.uint32), *rest).compile().as_text()
    assert "tpu_custom_call" in text
    assert " sort(" not in text


def test_described_chip_is_a_v5e(one_chip):
    kind = next(iter(one_chip.device_set)).device_kind
    assert "v5" in kind.lower(), kind
