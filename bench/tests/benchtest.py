"""Helpers for the benchmark's CPU tests: a scratch copy of the benchmark
with every configuration cut to a size the CPU runs in a second or two, and
one test-only cell, ``slots_small.open_zipf`` (``fixtures/``), that keeps the
open-loop load and the slot engine's reference under test while no
benchmark cell runs them."""
from __future__ import annotations

import io
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
FIXTURES = BENCH / "tests" / "fixtures"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

# every cut keeps the configuration's shape and only its scale
SMALL = {
    "fleet_1e7": lambda c: c["fl"].update(K=65536, k=64),
    "coord_1e6": lambda c: c["tenants"][0].update(K=8192, k=32),
}
OPEN_CELL = {"name": "slots_small.open_zipf", "config": "slots_small", "traffic": "open_zipf", "chips": 1,
             "why": "test only: tenants of two sizes in one SlotEngine, open-loop Zipf-skewed Poisson arrivals"}


def make_root(tmp: Path, mix_overrides: dict | None = None) -> Path:
    """A checkout-shaped copy (``BENCHMARK.json`` + ``bench/``) of the
    benchmark at CPU sizes, with the test-only open-loop cell and a
    peak-table row for the CPU."""
    root = Path(tmp) / "root"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(".cache", "__pycache__", "tests"))
    shutil.copy(FIXTURES / "slots_small.json", root / "bench" / "configs" / "slots_small.json")
    shutil.copy(FIXTURES / "open_zipf.json", root / "bench" / "traffic" / "open_zipf.json")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "slots_small", "source": "test fixture", "file": "bench/configs/slots_small.json",
                            "reduced": [], "why": "test only"})
    spec["workloads"].append(OPEN_CELL)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "coord_1e6.closed_ticks" in m.get("workloads", []):
            m["workloads"].append(OPEN_CELL["name"])
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for name, cut in SMALL.items():
        path = root / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cut(cfg)
        path.write_text(json.dumps(cfg))
    for mix, over in (mix_overrides or {}).items():
        path = root / "bench" / "traffic" / f"{mix}.json"
        path.write_text(json.dumps({**json.loads(path.read_text()), **over}))
    (root / "bench" / "peaks" / "cpu.json").write_text(json.dumps(
        {"device_kind": "cpu", "hbm_bytes_per_s": 1e10, "source": "placeholder for CPU tests"}))
    return root


def run(root: Path, workload: str, seed: int = 5, seconds: float = 1.0, trace: bool = False) -> dict:
    """One run of a cell on the CPU, the chip check skipped."""
    import jax

    from benchkit.cli import run_cell
    from benchkit.registry import Registry

    return run_cell(Registry(root), workload, seed, seconds, trace, t_start=time.perf_counter(),
                    devices=jax.devices()[:1], out=io.StringIO(), err=io.StringIO())
