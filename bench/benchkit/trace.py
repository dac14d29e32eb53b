"""From a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` keeps what the reduction needs from an ``.xplane.pb``: the
operations on each TPU (the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane, by HLO instruction name, control-flow ops that only enclose others
left out; the asynchronous copies of the ``Async XLA Ops`` line are not
counted) and the benchmark's own host spans (``bench.*`` trace annotations),
on the profiler's one clock, as a small JSON-able dict:

    {"devices": {"TPU:0": [[op, start_ns, dur_ns], ...], ...},
     "host": [[span, start_ns, dur_ns], ...]}

``reduce_trace`` turns that into busy and idle time, device time per round
stage (an op's stage is read from its HLO ``op_name`` metadata, where
``jax.named_scope`` puts ``round.allocate`` and the like; ``scope_map_from_hlo``
builds the map from the compiled program's text), the operations that took
most time, and the longest idle gaps, each named by the host span that was
open during it.
"""
from __future__ import annotations

import re
from collections import defaultdict

__all__ = ["load_xplane", "reduce_trace", "scope_map_from_hlo", "stage_of", "STAGES"]

# device-time buckets, first match wins (allocate and sample nest inside select)
STAGES = (
    ("allocate", ("round.allocate",)),
    ("select", ("round.sample", "round.select")),
    ("observe", ("round.observe",)),
    ("credit", ("round.credit",)),
    ("update", ("round.update",)),
)
_OPS_LINE = "XLA Ops"
_EVENT_NAME = re.compile(r"^%?([\w.\-]+) = ")
# control flow: a while/conditional/call event spans the ops of its body,
# which the line lists on their own, so it is left out
_CONTAINER = re.compile(r" (?:while|conditional|call)\(")
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*?op_name="([^"]*)"')


def scope_map_from_hlo(text: str) -> dict:
    """``{instruction name: op_name}`` from a compiled module's HLO text."""
    out = {}
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def stage_of(op_name: str) -> str:
    for stage, marks in STAGES:
        if any(mark in op_name for mark in marks):
            return stage
    return "other"


def load_xplane(path: str) -> dict:
    """The device ops and ``bench.*`` host spans of one trace file."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit():
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    ops = []
                    for e in line.events:
                        m = _EVENT_NAME.match(e.name)
                        if not _CONTAINER.search(e.name):
                            ops.append([m.group(1) if m else e.name, int(e.start_ns), int(e.duration_ns)])
                    devices[name[len("/device:"):]] = ops
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, int(e.start_ns), int(e.duration_ns)])
    return {"devices": devices, "host": host}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _host_span_at(host, s, e):
    """The innermost ``bench.*`` span overlapping ``[s, e)`` most."""
    best, best_key = "no bench span", None
    for name, hs, hd in host:
        ov = min(e, hs + hd) - max(s, hs)
        if ov <= 0 or name == "bench.window":
            continue
        key = (ov, -hd)
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def _label(name: str, scope_map: dict) -> str:
    """An op's name with the last two parts of its scope, where known."""
    scope = scope_map.get(name)
    return f"{name} {'/'.join(scope.split('/')[-2:])}" if scope else name


def reduce_trace(trace: dict, scope_map: dict | None = None, top: int = 10) -> dict:
    """Busy/idle seconds (averaged over the chips), device seconds per stage
    (summed over chips' ops, divided by the chip count), top ops and the
    longest idle gaps within the ``bench.window`` span."""
    scope_map = scope_map or {}
    windows = [(s, s + d) for name, s, d in trace["host"] if name == "bench.window"]
    if not windows:
        raise ValueError("trace has no bench.window span")
    w0, w1 = windows[0]
    n_dev = max(len(trace["devices"]), 1)
    busy = 0.0
    stage_s = defaultdict(float)
    op_s = defaultdict(float)
    gaps = []
    for dev, ops in sorted(trace["devices"].items()):
        clipped = []
        for name, s, d in ops:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 <= s2:
                continue
            clipped.append((s2, e2))
            sec = (e2 - s2) * 1e-9
            op_s[name] += sec
            stage_s[stage_of(scope_map.get(name, ""))] += sec
        merged = _union(clipped)
        busy += sum(e - s for s, e in merged) * 1e-9
        if dev.endswith(":0") or len(trace["devices"]) == 1:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append([_host_span_at(trace["host"], s, e), (e - s) * 1e-9])
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(([_label(n, scope_map), s / n_dev] for n, s in op_s.items()), key=lambda x: -x[1])[:top]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / n_dev,
        "stage_s": {k: v / n_dev for k, v in stage_s.items()},
        "top_ops": top_ops,
        "idle_gaps": gaps[:top],
        "chips": len(trace["devices"]),
    }
