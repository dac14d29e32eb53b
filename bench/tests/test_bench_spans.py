"""The readers of the program's spans and counters, on a traced CPU run of
the served cell, and on a program that has no span recorder."""
from __future__ import annotations

import json

import benchtest
import pytest

from benchkit.registry import Registry

CELL = "coord_1e6.closed_ticks"
SPAN_METRICS = ["frontend_ms.parse", "frontend_ms.queue", "frontend_ms.decode", "frontend_ms.reply",
                "engine_ms.copy_in", "engine_ms.launch", "engine_ms.wait", "engine_ms.copy_out",
                "engine_ms.cohort", "engine_syncs_per_tick", "engine_copy_mb_per_tick"]


def _new_metrics():
    spec = json.loads((benchtest.ROOT / "BENCHMARK.json").read_text())
    return {m["name"]: m for m in spec["per_layer"] if m["name"] in SPAN_METRICS}


def test_span_metrics_are_declared_for_the_served_cell():
    declared = _new_metrics()
    assert sorted(declared) == sorted(SPAN_METRICS)
    for m in declared.values():
        assert m["workloads"] == [CELL] and m["source"] == "host_clock"
        assert m["layer"] in ("front end", "engines")


def test_traced_served_run_reports_every_span_metric(tmp_path):
    from repro.obs.trace import SPANS

    SPANS.reset()  # other runs in this process recorded into it too
    line = benchtest.run(benchtest.make_root(tmp_path), CELL, seed=2**33 + 5, seconds=1.0, trace=True)
    assert line["correct"] is True, line["checks"]
    got = line["metrics"]
    assert set(SPAN_METRICS) <= set(got), sorted(set(SPAN_METRICS) - set(got))
    K = 8192  # benchtest's cut of coord_1e6
    assert got["engine_syncs_per_tick"]["value"] == 2.0
    assert got["engine_copy_mb_per_tick"]["value"] == pytest.approx((8 * K + 1) / 1e6, rel=1e-12)
    for name in SPAN_METRICS[:9]:
        assert got[name]["unit"] == "ms" and got[name]["value"] >= 0
    ticks = SPANS.counters["engine.ticks"]
    assert ticks == line["attempted"] + 2  # the window's ticks and the two warm-up ticks
    assert SPANS.durations_ns("engine.launch").size == ticks


def test_without_a_span_recorder_the_readers_give_nothing(monkeypatch):
    """A program that predates the recorder: every reader returns None."""
    from repro.obs import trace

    monkeypatch.delattr(trace, "SPANS")
    reg = Registry(benchtest.ROOT)
    for name in SPAN_METRICS:
        assert reg.metric(name).read({}) is None


def test_a_wrapped_ring_gives_no_median(monkeypatch):
    """Once the ring has dropped spans, a median would cover only the newest:
    the span readers give nothing, while the counter readers still read."""
    from repro.obs import trace

    rec = trace.SpanTimer(capacity=2)
    for _ in range(2):
        rec.record("engine.launch", 0, 1_000_000)
    rec.add("engine.ticks", 2)
    rec.add("engine.syncs", 4)
    monkeypatch.setattr(trace, "SPANS", rec)
    reg = Registry(benchtest.ROOT)
    assert reg.metric("engine_ms.launch").read({}) == pytest.approx(1.0)
    rec.record("engine.launch", 0, 1_000_000)
    assert rec.dropped == 1
    assert reg.metric("engine_ms.launch").read({}) is None
    assert reg.metric("engine_syncs_per_tick").read({}) == 2.0
