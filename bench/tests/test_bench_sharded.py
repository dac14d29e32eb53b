"""The K-sharded horizon cell (``fleet_4e7_d4.diurnal_horizon``) on four
virtual CPU devices, at a small size that keeps its shape: the program over a
4-device mesh against the reference's sharded replay, the per-layer metrics a
traced run reports at four chips, and a program that leaves the candidate
exchange out, which the comparison has to catch."""
from __future__ import annotations

import io
import json
import time

import benchtest
import jax
import pytest

CELL = "fleet_4e7_d4.diurnal_horizon"
CONFIG = "fleet_4e7_d4"
SMALL = dict(K=262144, k=64)  # 65,536 clients a shard, as fleet_1e7's CPU cut is one chip's


@pytest.fixture
def devices4():
    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=4 or more")
    return devices[:4]


def _root(tmp_path):
    root = benchtest.make_root(tmp_path)
    path = root / "bench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg["fl"].update(SMALL)
    path.write_text(json.dumps(cfg))
    return root


def _run(root, devices, *, seed=2**35 + 3, trace=False):
    from benchkit.cli import run_cell
    from benchkit.registry import Registry

    return run_cell(Registry(root), CELL, seed, 1.0, trace, t_start=time.perf_counter(), devices=devices,
                    out=io.StringIO(), err=io.StringIO())


def test_sharded_program_agrees_with_the_sharded_reference(tmp_path, devices4):
    line = _run(_root(tmp_path), devices4)
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["slots_moved"]["value"] == 0.0
    assert line["checks"]["rounds_off"]["value"] == 0.0
    assert line["device"]["count"] == 4 and line["attempted"] > 0
    assert {"rounds_per_s", "setup_s"} <= set(line["metrics"])


def _device_trace(stage_ops, peak, K, chips, C):
    """A stand-in for the chips' trace (the CPU has no device planes): each
    of ``chips`` devices runs, every traced round, one op of each stage, the
    select and observe ops taking exactly the time their essential bytes of
    one chip's share take at ``peak`` (so each roofline reads 100%)."""
    from benchkit import trace as bt

    reduce = bt.reduce_trace
    per_round_ns = {
        "allocate": 10_000,
        "select": 8.0 * K / chips / peak * 1e9,
        "observe": (K / 8 + 4.0 * K) / chips / peak * 1e9,
        "update": 10_000,
    }

    def fake(trace, scope_map=None, top=10):
        (w0, _), = [(s, s + d) for n, s, d in trace["host"] if n == "bench.window"]
        rounds = C * sum(1 for n, _, _ in trace["host"] if n == "bench.runner_call")
        devices = {}
        for d in range(chips):
            ops, t = [], w0
            for _ in range(rounds):
                for stage, name in stage_ops.items():
                    dur = int(round(per_round_ns[stage]))
                    ops.append([name, t, dur])
                    t += dur
            devices[f"TPU:{d}"] = ops
        return reduce({"devices": devices, "host": trace["host"]}, scope_map, top)

    return fake


def test_traced_run_reports_the_cells_per_layer_metrics(tmp_path, devices4, monkeypatch):
    from benchkit import trace as bt
    from benchkit.cli import RunContext
    from benchkit.registry import Registry

    root = _root(tmp_path)
    reg = Registry(root)
    K, C = SMALL["K"], reg.traffic(reg.cell(CELL)["traffic"])["chunk_rounds"]
    peak = reg.peaks("cpu")["hbm_bytes_per_s"]
    seen = {}
    keep = RunContext.set_scope_map_from_hlo

    def scope_map(self, hlo_text):
        keep(self, hlo_text)
        # one compiled op of each stage, the exchange's all-gather for select
        for name, op in self.scope_map.items():
            if "round.exchange" in op and name.startswith("all_gather"):
                seen.setdefault("select", name)
            for stage, mark in (("allocate", "round.allocate"), ("observe", "round.observe"),
                                ("update", "round.update")):
                if mark in op:
                    seen.setdefault(stage, name)

    monkeypatch.setattr(RunContext, "set_scope_map_from_hlo", scope_map)
    monkeypatch.setattr(bt, "reduce_trace", _device_trace(seen, peak, K, 4, C))
    line = _run(root, devices4, trace=True)
    assert line["correct"] is True, line["checks"]
    assert set(seen) == {"allocate", "select", "observe", "update"}, seen
    declared = {m["name"] for m in reg.metrics_for(CELL, True)}
    assert declared == {"stage_ms.allocate", "stage_ms.select", "select_roofline", "unpack_bits_roofline",
                        "idle_share.horizon"}
    assert declared <= set(line["metrics"]), line["metrics"]
    # the readers count the whole population's bytes over the four chips'
    # time: one chip's share at the peak reads 100% (less the ops' rounding
    # to whole nanoseconds), not 400%
    for name in ("select_roofline", "unpack_bits_roofline"):
        assert 99.99 < line["metrics"][name]["value"] <= 100.0
    assert line["device"]["count"] == 4
    # the exchange is named in the breakdown by the last two parts of its scope
    assert any("round.exchange/all_gather" in op for op, _ in line["breakdown"]["device_ops"])


def test_leaving_the_exchange_out_is_not_correct(tmp_path, devices4, monkeypatch):
    """Each shard keeps its own local top-k candidates instead of the merged
    global top-k: every shard then marks k clients of its own."""
    from repro.engine import sharded

    monkeypatch.setattr(sharded, "_exchange_topk", lambda vals, gidx, k, axis_name: gidx)
    line = _run(_root(tmp_path), devices4)
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["slots_moved"]["value"] > line["checks"]["slots_moved"]["limit"]


def test_the_cell_shards_fleet_1e7s_share_over_four_chips():
    from benchkit.registry import Registry

    reg = Registry(benchtest.ROOT)
    cell, one = reg.cell(CELL), reg.cell("fleet_1e7.diurnal_horizon")
    cfg, cfg1 = reg.config(cell["config"]), reg.config(one["config"])
    assert cell["chips"] == 4 and one["chips"] == 1
    assert cfg["fl"]["K"] // cell["chips"] == cfg1["fl"]["K"] and cfg["fl"]["K"] % cell["chips"] == 0
    assert cell["traffic"] == one["traffic"]
    assert {**cfg["fl"], "K": cfg1["fl"]["K"]} == cfg1["fl"]
    assert cfg["program"] == cfg1["program"] and cfg["reference"] == cfg1["reference"]
    assert cfg["reduced"] == [] and cfg["precision"] == "float32"
