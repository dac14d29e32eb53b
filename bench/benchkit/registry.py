"""Find a cell's pieces by name.

Everything that belongs to one configuration, traffic mix, driver, plain
reference, metric or device kind sits in a file of its own, found by the name
that ``BENCHMARK.json`` (or the configuration / mix) gives:

=================  ==============================================
piece              file
=================  ==============================================
configuration      the ``file`` of its ``configs`` entry (JSON)
traffic mix        ``bench/traffic/<traffic>.json``
driver             ``bench/drivers/<mix["driver"]>.py``
plain reference    ``bench/reference/<config["reference"]>.py``
metric reader      ``bench/metrics/<metric name>.py``, else the
                   family's ``bench/metrics/<name up to its first .>.py``
peak table         ``bench/peaks/*.json``, keyed by ``device_kind``
=================  ==============================================

Adding a cell, a configuration, a mix or a metric is adding files and
``BENCHMARK.json`` entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType

__all__ = ["Registry", "ROOT", "load_sibling"]

ROOT = Path(__file__).resolve().parents[2]  # the checkout: BENCHMARK.json, bench/, src/
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _check_name(kind: str, name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    return name


def _load(mod_name: str, path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_sibling(file: str, name: str) -> ModuleType:
    """The module ``<name>.py`` beside ``file`` (how metric readers reach the
    helpers they share in ``bench/metrics/_common.py``)."""
    path = Path(file).with_name(f"{name}.py")
    return _load(f"bench_{path.parent.name}_{name}", path)


class Registry:
    """Reads one checkout's benchmark definition; ``root`` holds
    ``BENCHMARK.json`` and ``bench/``."""

    def __init__(self, root: Path | str = ROOT):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._modules: dict = {}

    # -- BENCHMARK.json ----------------------------------------------------

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.spec["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")

    def metrics_for(self, cell: str, trace: bool) -> list:
        """The cell's end-to-end metrics (``trace=False``) or per-layer
        metrics (``trace=True``): entries without a ``workloads`` key apply
        to every cell."""
        entries = self.spec["per_layer"] if trace else self.spec["end_to_end"]
        return [m for m in entries if "workloads" not in m or cell in m["workloads"]]

    # -- files found by name -------------------------------------------------

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                cfg = json.loads((self.root / c["file"]).read_text())
                if cfg.get("name") != name:
                    raise ValueError(f"{c['file']} names itself {cfg.get('name')!r}, not {name!r}")
                return cfg
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        path = self.bench / "traffic" / f"{_check_name('traffic', name)}.json"
        mix = json.loads(path.read_text())
        mix.setdefault("name", name)
        return mix

    def _module(self, kind: str, name: str, family: bool = False) -> ModuleType:
        path = self.bench / kind / f"{_check_name(kind, name)}.py"
        if family and not path.is_file():
            path = self.bench / kind / f"{name.split('.')[0]}.py"
        if not path.is_file():
            raise KeyError(f"no {kind} file {path.relative_to(self.root)}")
        mod = self._modules.get(path)
        if mod is None:
            mod = self._modules[path] = _load(f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', path.stem)}", path)
        return mod

    def driver(self, name: str) -> ModuleType:
        return self._module("drivers", name)

    def reference(self, name: str) -> ModuleType:
        return self._module("reference", name)

    def metric(self, name: str) -> ModuleType:
        """The reader of ``name``: its own file, else its family's, so that
        ``idle_share.horizon`` and ``idle_share.serve`` share ``idle_share.py``."""
        return self._module("metrics", name, family=True)

    def peaks(self, device_kind: str) -> dict:
        """The peak table row of ``device_kind``; an unknown kind is an error."""
        for path in sorted((self.bench / "peaks").glob("*.json")):
            row = json.loads(path.read_text())
            if row.get("device_kind") == device_kind:
                return row
        raise KeyError(f"no peak table for device kind {device_kind!r} under bench/peaks/")
