"""The harness end to end on the CPU, at small sizes: sound runs are
correct, pieces are found by name, and without a TPU there is no result."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import benchtest
import pytest

CELLS = ["fleet_1e7.diurnal_horizon", "slots_small.open_zipf", "coord_1e6.closed_ticks"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tmp_path, cell):
    line = benchtest.run(benchtest.make_root(tmp_path), cell, seed=2**35 + 17, seconds=1.0)
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks" and line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": None}


def test_new_configuration_mix_and_metric_are_found_by_name(tmp_path):
    """A throwaway configuration, mix and metric, added as new files and
    BENCHMARK.json entries, run without an edit to any existing file."""
    root = benchtest.make_root(tmp_path)
    bench = root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((bench / "configs" / "fleet_1e7.json").read_text())
    cfg["name"] = "fleet_tiny"
    cfg["fl"].update(K=16384, k=16)
    (bench / "configs" / "fleet_tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "diurnal_horizon.json").read_text())
    mix.update(amplitude=0.1, chunk_rounds=8)
    (bench / "traffic" / "flat_day.json").write_text(json.dumps(mix))
    (bench / "metrics" / "chunk_count.py").write_text(
        "def read(run):\n    return run['rounds'] / 8 if 'rounds' in run else None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "fleet_tiny", "source": "https://arxiv.org/abs/2011.08756",
                            "file": "bench/configs/fleet_tiny.json", "reduced": ["K", "k"], "why": "test"})
    spec["workloads"].append({"name": "fleet_tiny.flat_day", "config": "fleet_tiny", "traffic": "flat_day",
                              "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "chunk_count", "unit": "chunks", "better": "higher", "bound": 0.1,
                               "source": "host_clock", "workloads": ["fleet_tiny.flat_day"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    line = benchtest.run(root, "fleet_tiny.flat_day", seed=4, seconds=0.5)
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["chunk_count"]["value"] == line["attempted"] / 8
    assert all(p.read_bytes() == b for p, b in before.items())


def test_metric_family_shares_one_reader(tmp_path):
    """A suffixed metric with no file of its own is read by its family's
    reader; a metric with neither is an error."""
    from benchkit.registry import Registry

    reg = Registry(benchtest.make_root(tmp_path))
    assert reg.metric("idle_share.horizon") is reg.metric("idle_share.serve") is not None
    assert reg.metric("idle_share.anything_new").read({"trace": {"busy_s": 1.0, "window_s": 4.0}}) == 75.0
    assert reg.metric("stage_ms.select") is not reg.metric("stage_ms.allocate")
    with pytest.raises(KeyError):
        reg.metric("no_such_family.serve")


def test_host_watch_counts_what_the_window_did():
    import gc
    import threading

    from benchkit.host import HostWatch

    host = HostWatch()
    try:
        before = host.snapshot()
        spin = threading.Thread(target=lambda: sum(i * i for i in range(2_000_000)))
        spin.start()
        gc.collect()
        spin.join()
        d = host.delta(host.snapshot(), before)
    finally:
        host.close()
    assert d["gc_n"][2] >= 1 and d["gc_s"][2] > 0 and d["wall_s"] > 0
    assert d["cpu_user_s"] + d["cpu_sys_s"] > 0 and d["busiest_threads"] and d["threads"] >= 1
    json.dumps(d)


def test_prime_fills_the_cache_once_per_checkout(tmp_path, monkeypatch):
    """A cell's first run primes the cache in a child run with a one-second
    window; once the child succeeded, later runs do not prime again, and a
    failed child leaves no mark."""
    import subprocess

    from benchkit import cli, device

    monkeypatch.setattr(device, "CACHE_DIR", tmp_path / "cache" / "jax")
    calls, rcs = [], [3, 0]

    def fake_run(cmd, **kw):
        calls.append(cmd)
        return subprocess.CompletedProcess(cmd, rcs[len(calls) - 1], "", "bench: no TPU")

    monkeypatch.setattr(cli.subprocess, "run", fake_run)
    assert cli.prime("coord_1e6.closed_ticks", 2**40)["rc"] == 3
    assert cli.prime("coord_1e6.closed_ticks", 2**40)["rc"] == 0
    assert cli.prime("coord_1e6.closed_ticks", 2**40) is None and len(calls) == 2
    assert calls[0][-8:] == ["--seed", str(2**40), "--seconds", "1", "--trace", "0", "--child", "1"]


def test_unknown_device_kind_has_no_peaks(tmp_path):
    from benchkit.registry import Registry

    with pytest.raises(KeyError):
        Registry(benchtest.make_root(tmp_path)).peaks("TPU v99")


def _command(root, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fleet_1e7.diurnal_horizon", "--seed", "1",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_there_is_no_result():
    proc = _command(benchtest.ROOT)
    assert proc.returncode != 0 and proc.stdout.strip() == "", proc.stderr[-2000:]
    assert "no TPU" in proc.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    shutil.copytree(benchtest.BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(benchtest.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _command(tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == "", proc.stderr[-2000:]
