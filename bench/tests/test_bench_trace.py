"""The trace reduction against a brute-force count, on a recorded trace."""
from __future__ import annotations

import json
from pathlib import Path

import benchtest  # noqa: F401
import numpy as np
import pytest

from benchkit.trace import reduce_trace, scope_map_from_hlo, stage_of

FIXTURES = Path(__file__).with_name("fixtures")


def _brute(trace, scope_map):
    """Busy time by marking every microsecond; stage time by summing ops."""
    (w0, w1), = [(s, s + d) for n, s, d in trace["host"] if n == "bench.window"]
    busy, stages = [], {}
    for ops in trace["devices"].values():
        t = np.zeros((w1 - w0) // 1000 + 1, bool)
        for name, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                t[(a - w0) // 1000:(b - w0 + 999) // 1000] = True
                st = stage_of(scope_map.get(name, ""))
                stages[st] = stages.get(st, 0.0) + (b - a) * 1e-9
        busy.append(t.sum() * 1e-6)
    n = len(trace["devices"])
    return float(np.mean(busy)), {k: v / n for k, v in stages.items()}, (w1 - w0) * 1e-9


def _synthetic():
    ops = [["fusion.1", 1_000_000, 2_000_000], ["top-k.2", 3_500_000, 4_000_000], ["fusion.3", 9_000_000, 500_000]]
    host = [["bench.window", 0, 12_000_000], ["bench.runner_call", 7_600_000, 1_000_000]]
    hlo = ('  %fusion.1 = f32[8]{0} fusion(x), metadata={op_name="jit(r)/while/body/round.select/round.allocate/exp"}\n'
           '  %top-k.2 = (f32[4]{0}) custom-call(y), metadata={op_name="jit(r)/while/body/round.select/round.sample/top_k"}\n'
           '  ROOT %fusion.3 = f32[8]{0} fusion(z), metadata={op_name="jit(r)/while/body/round.update/add"}\n')
    return {"devices": {"TPU:0": ops}, "host": host}, scope_map_from_hlo(hlo)


def _recorded():
    path = FIXTURES / "fleet_trace.json"
    if not path.exists():
        pytest.skip("no recorded trace fixture")
    rec = json.loads(path.read_text())
    return rec["trace"], rec["scope_map"]


@pytest.mark.parametrize("source", ["synthetic", "recorded"])
def test_reduction_matches_a_brute_force_count(source):
    trace, scope_map = _synthetic() if source == "synthetic" else _recorded()
    out = reduce_trace(trace, scope_map)
    busy, stages, window = _brute(trace, scope_map)
    assert out["window_s"] == pytest.approx(window)
    assert out["busy_s"] == pytest.approx(busy, abs=2e-6 * max(1, len(trace["devices"][next(iter(trace["devices"]))])))
    assert set(out["stage_s"]) == set(stages)
    for k, v in stages.items():
        assert out["stage_s"][k] == pytest.approx(v, rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert sum(g[1] for g in out["idle_gaps"]) <= out["window_s"] - out["busy_s"] + 1e-9
    if source == "synthetic":
        assert out["stage_s"] == pytest.approx({"allocate": 2e-3, "select": 4e-3, "update": 5e-4})
        assert out["busy_s"] == pytest.approx(6.5e-3)
        # the gap from 7.5 to 9 ms is named by the host span open during it
        assert out["idle_gaps"][0] == ["no bench span", pytest.approx(2.5e-3)]
        assert out["idle_gaps"][1] == ["bench.runner_call", pytest.approx(1.5e-3)]
