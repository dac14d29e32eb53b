"""The correctness check catches a broken timed path, and the control.

Each case runs a cell end to end on the CPU at a small size (the chip check
skipped) with the program broken underneath, and expects ``correct`` false:
a step that returns its state unchanged, and an answer altered where it is
produced.  The control (the reference in bfloat16 in the program's place)
has to fail the same comparison.
"""
from __future__ import annotations

import benchtest
import jax
import pytest

CELLS = ["fleet_1e7.diurnal_horizon", "slots_small.open_zipf", "coord_1e6.closed_ticks"]


def _stale_runner(monkeypatch):
    from repro.engine.round_program import RoundProgram

    build = RoundProgram.build_runner

    def broken(self, *a, **kw):
        run, state0 = build(self, *a, **kw)
        return jax.jit(lambda state, *rest: (state,) + tuple(run(state, *rest)[1:])), state0

    monkeypatch.setattr(RoundProgram, "build_runner", broken)


def _altered_sample(monkeypatch):
    from repro.core.selection import sampling

    sample = sampling.plackett_luce_sample

    def altered(rng, p, k):
        idx = sample(rng, p, k)
        return idx.at[0].set((idx[0] + 1) % p.shape[0])

    monkeypatch.setattr(sampling, "plackett_luce_sample", altered)


def _stale_slots(monkeypatch):
    from repro.serve import engines

    make = engines.make_multi_job

    def broken(k_max, **kw):
        job_step, batched = make(k_max, **kw)

        def stale(cfg_row, logw, t, key, x):
            _, t2, out = job_step(cfg_row, logw, t, key, x)
            return logw, t2, out

        return stale, batched

    monkeypatch.setattr(engines, "make_multi_job", broken)


def _stale_sharded(monkeypatch):
    from repro.serve import engines

    runner = engines.ShardedEngine._runner

    def broken(self, spec):
        run, state0, program = runner(self, spec)
        return (lambda state, *rest: (state,) + tuple(run(state, *rest)[1:])), state0, program

    monkeypatch.setattr(engines.ShardedEngine, "_runner", broken)


def _altered_answer(engine_cls):
    def patch(monkeypatch):
        tick = engine_cls.tick

        def altered(self, items):
            res = tick(self, items)
            for uid, r in res.items():
                K = self.jobs[uid]["spec"].K
                r["cohort"][0] = (r["cohort"][0] + 1) % K
            return res

        monkeypatch.setattr(engine_cls, "tick", altered)

    return patch


def _faults():
    from repro.serve import engines

    return {
        ("fleet_1e7.diurnal_horizon", "state_unchanged"): _stale_runner,
        ("fleet_1e7.diurnal_horizon", "answer_altered"): _altered_sample,
        ("slots_small.open_zipf", "state_unchanged"): _stale_slots,
        ("slots_small.open_zipf", "answer_altered"): _altered_answer(engines.SlotEngine),
        ("coord_1e6.closed_ticks", "state_unchanged"): _stale_sharded,
        ("coord_1e6.closed_ticks", "answer_altered"): _altered_answer(engines.ShardedEngine),
    }


@pytest.mark.parametrize("cell,fault", [(c, f) for c in CELLS for f in ("state_unchanged", "answer_altered")])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, cell, fault):
    root = benchtest.make_root(tmp_path)
    _faults()[(cell, fault)](monkeypatch)
    line = benchtest.run(root, cell, seed=9, seconds=1.0)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_comparison(tmp_path, cell):
    import importlib.util

    from benchkit.registry import Registry

    root = benchtest.make_root(tmp_path)
    spec = importlib.util.spec_from_file_location("bench_control", root / "bench" / "control.py")
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    checks = control.control(Registry(root), cell, seed=3, steps=96 if "fleet" in cell else 60)
    assert any(c["value"] > c["limit"] for c in checks), checks
