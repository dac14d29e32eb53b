"""Stage-level tracing and the host span recorder.

Two instruments, one per side of the dispatch boundary:

* ``stage(name)`` — a trace annotation for *device* code: a
  ``jax.named_scope``, zero runtime cost; the stage name lands in HLO op
  metadata so ``jax.profiler`` traces (and XLA dumps) show allocate /
  select / observe / credit / update as named regions of the round.
* ``SpanTimer`` — the span recorder for *host* code (the serving path).
  Each span records its name, start and end (``time.perf_counter_ns``),
  its own id, its parent's id (the span open on the same thread) and a
  request id, into a fixed-size ring of columnar numpy arrays; exact
  quantiles come from the ring.  Every span also opens a
  ``jax.profiler.TraceAnnotation``, so it lands on the profiler's host
  timeline on the same clock as the device ops, and feeds a lifetime
  ``LatencyHistogram`` per name for the run-log exporter.  ``add(name, n)``
  counters sit beside the spans.  ``SPANS`` is the process-wide recorder.

``LatencyHistogram`` buckets are log-spaced between ``lo`` and ``hi``
seconds; quantiles interpolate within the winning bucket on cumulative
counts, while min/max/sum/count are tracked exactly so means and extremes
are not bucket-quantized.
"""
from __future__ import annotations

import bisect
import contextlib
import itertools
import math
import threading
import time
from typing import Dict, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["stage", "SpanTimer", "LatencyHistogram", "SPANS"]


@contextlib.contextmanager
def stage(name: str):
    """Name a pipeline stage of device code: a ``jax.named_scope``.

    Under ``jax.jit`` tracing the stage name lands in the ops' metadata
    (free at run time), so profiler traces and XLA dumps show the round's
    stages as named regions; outside a trace it is a cheap push/pop on
    jax's name stack.  Host spans use ``SpanTimer.span``, which opens a
    profiler ``TraceAnnotation`` instead.
    """
    with jax.named_scope(name):
        yield


class LatencyHistogram:
    """Log-bucketed latency accumulator with exact min/max/sum/count.

    ``n_buckets`` edges are geometrically spaced over ``[lo, hi]`` seconds;
    observations outside the range clamp into the end buckets.  Quantiles
    interpolate linearly within the selected bucket, and are additionally
    clamped to the exact observed [min, max] so tiny samples cannot report
    a quantile outside the data.
    """

    def __init__(self, lo: float = 1e-6, hi: float = 10.0, n_buckets: int = 64):
        if not (0 < lo < hi):
            raise ValueError(f"need 0 < lo < hi, got lo={lo}, hi={hi}")
        self.edges = np.geomspace(lo, hi, n_buckets + 1)
        self._edges = self.edges.tolist()  # bisect on a list: faster than searchsorted for one sample
        self.counts = np.zeros(n_buckets, np.int64)
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = 0.0

    def observe(self, seconds: float) -> None:
        """Record one sample; negative or non-finite values are dropped."""
        s = float(seconds)
        if not 0.0 <= s < math.inf:
            return
        # bisect within edges[1:-1]: below-range and above-range samples clamp
        # into the end buckets.
        self.counts[bisect.bisect_right(self._edges, s, 1, len(self._edges) - 1) - 1] += 1
        self.count += 1
        self.sum += s
        if s < self.min:
            self.min = s
        if s > self.max:
            self.max = s

    def quantile(self, q: float) -> float:
        """Approximate quantile (``q`` in [0, 1]) from bucket counts."""
        if self.count == 0:
            return float("nan")
        target = q * self.count
        cum = np.cumsum(self.counts)
        i = int(np.searchsorted(cum, target, side="left"))
        i = min(i, len(self.counts) - 1)
        prev = cum[i - 1] if i > 0 else 0
        in_bucket = self.counts[i]
        frac = (target - prev) / in_bucket if in_bucket else 0.0
        lo, hi = self.edges[i], self.edges[i + 1]
        return float(min(max(lo + frac * (hi - lo), self.min), self.max))

    @property
    def mean(self) -> float:
        """Exact mean of the observed samples (NaN when empty)."""
        return self.sum / self.count if self.count else float("nan")

    def summary(self) -> Dict[str, float]:
        """The JSON-ready digest the runlog/report layer emits."""
        return {
            "count": int(self.count),
            "mean_s": self.mean,
            "min_s": self.min if self.count else float("nan"),
            "max_s": self.max,
            "p50_s": self.quantile(0.50),
            "p90_s": self.quantile(0.90),
            "p99_s": self.quantile(0.99),
        }

    def to_record(self) -> dict:
        """Full serializable state (edges + counts) for the JSONL stream."""
        return {
            "edges_s": self.edges.tolist(),
            "counts": self.counts.tolist(),
            **self.summary(),
        }


class _Span:
    """One open span: the context manager ``SpanTimer.span`` returns."""

    __slots__ = ("_timer", "_name", "id", "rid", "_parent", "_ann", "_t0")

    def __init__(self, timer: "SpanTimer", name: str, rid: Optional[int]):
        self._timer, self._name, self.rid = timer, name, rid

    def __enter__(self) -> "_Span":
        timer = self._timer
        stack = timer._stack()
        if stack:
            self._parent, parent_rid = stack[-1]
        else:
            self._parent, parent_rid = -1, -1
        if self.rid is None:
            self.rid = parent_rid
        self.id = next(timer._ids)
        stack.append((self.id, self.rid))
        self._ann = TraceAnnotation(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        timer = self._timer
        timer._stack().pop()
        timer._write(self._name, self._t0, t1, self.id, self._parent, self.rid)


class SpanTimer:
    """Host span recorder: a ring of span events, lifetime histograms and
    counters (see module doc).

    >>> spans = SpanTimer()
    >>> with spans.span("request", rid=7):
    ...     with spans.span("decode"):   # parent = "request", rid 7
    ...         work()
    >>> spans.quantile("decode", 0.95)   # exact, over the ring (seconds)
    >>> spans.add("bytes", 4096)

    The ring holds the newest ``capacity`` events as columns (name id,
    start and end ns, span id, parent id, request id); older ones are
    overwritten and counted in ``dropped``.  ``anchor`` is one
    ``(time.time_ns(), time.perf_counter_ns())`` pair taken at creation:
    ``wall_ns`` places a ring time on the wall clock the profiler's host
    timeline uses.  Spans and counters from any thread are safe.
    """

    def __init__(self, lo: float = 1e-6, hi: float = 10.0, n_buckets: int = 64, capacity: int = 1 << 16):
        if capacity < 1:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self._args = (lo, hi, n_buckets)
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self.anchor = (time.time_ns(), time.perf_counter_ns())
        self.reset()

    def reset(self) -> None:
        """Forget every span, histogram and counter (the anchor stays)."""
        with self._lock:
            cap = self.capacity
            self._name = np.zeros(cap, np.int32)
            self._t0 = np.zeros(cap, np.int64)
            self._t1 = np.zeros(cap, np.int64)
            self._id = np.zeros(cap, np.int64)
            self._parent = np.zeros(cap, np.int64)
            self._rid = np.zeros(cap, np.int64)
            self._n = 0  # events ever written
            self._ids = itertools.count(1)
            self.names: list = []  # name id -> name
            self._name_ids: Dict[str, int] = {}
            self.hist: Dict[str, LatencyHistogram] = {}
            self.counters: Dict[str, int] = {}

    # -- recording ---------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _write(self, name: str, t0: int, t1: int, sid: int, parent: int, rid: int) -> None:
        with self._lock:
            nid = self._name_ids.get(name)
            if nid is None:
                nid = self._name_ids[name] = len(self.names)
                self.names.append(name)
            i = self._n % self.capacity
            self._name[i], self._t0[i], self._t1[i] = nid, t0, t1
            self._id[i], self._parent[i], self._rid[i] = sid, parent, rid
            self._n += 1
            self._hist(name).observe((t1 - t0) * 1e-9)

    def _hist(self, name: str) -> LatencyHistogram:
        h = self.hist.get(name)
        if h is None:
            h = self.hist[name] = LatencyHistogram(*self._args)
        return h

    def get(self, name: str) -> LatencyHistogram:
        """The lifetime ``name`` histogram, created on first use."""
        with self._lock:
            return self._hist(name)

    def span(self, name: str, rid: Optional[int] = None) -> _Span:
        """Time a block as one span.  Its parent is the span open on this
        thread; ``rid`` (the request id) defaults to the parent's, else -1.
        The block also shows as a ``TraceAnnotation`` on the profiler's
        host timeline.  ``with`` yields the span (``.id``, ``.rid``)."""
        return _Span(self, name, rid)

    def record(self, name: str, t0_ns: int, t1_ns: int, *, rid: Optional[int] = None) -> int:
        """Record a span whose start was taken elsewhere (another thread's
        ``time.perf_counter_ns()``), e.g. a queue wait.  Parent and request
        id default as in ``span``.  Not on the profiler's timeline.
        Returns the span's id."""
        stack = self._stack()
        top_id, top_rid = stack[-1] if stack else (-1, -1)
        sid = next(self._ids)
        self._write(name, int(t0_ns), int(t1_ns), sid, top_id, top_rid if rid is None else int(rid))
        return sid

    def add(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the counter ``name``."""
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + int(n)

    # -- reading -----------------------------------------------------------

    @property
    def dropped(self) -> int:
        """Events overwritten since the ring wrapped."""
        return max(0, self._n - self.capacity)

    def wall_ns(self, t_ns):
        """A ``perf_counter_ns`` time (or array) on the wall clock, through
        the anchor — the clock of the profiler's host timeline."""
        wall0, perf0 = self.anchor
        return wall0 + (np.asarray(t_ns, np.int64) - perf0)

    def events(self) -> Dict[str, np.ndarray]:
        """The ring's events, oldest first, as columns: ``name`` (str),
        ``t0_ns``, ``t1_ns``, ``id``, ``parent``, ``rid`` (-1: none)."""
        with self._lock:
            n = min(self._n, self.capacity)
            order = (np.arange(n) + self._n) % self.capacity if self._n > self.capacity else np.arange(n)
            names = np.asarray(self.names, dtype=object)
            return {
                "name": names[self._name[order]] if n else np.zeros(0, object),
                "t0_ns": self._t0[order], "t1_ns": self._t1[order], "id": self._id[order],
                "parent": self._parent[order], "rid": self._rid[order],
            }

    def durations_ns(self, name: str) -> np.ndarray:
        """Durations (ns) of every ``name`` span the ring holds."""
        with self._lock:
            nid = self._name_ids.get(name)
            if nid is None:
                return np.zeros(0, np.int64)
            n = min(self._n, self.capacity)
            sel = self._name[:n] == nid
            return self._t1[:n][sel] - self._t0[:n][sel]

    def quantile(self, name: str, q: float) -> Optional[float]:
        """Exact quantile (seconds) of ``name``'s durations over the ring;
        None if the ring holds no such span."""
        d = self.durations_ns(name)
        return float(np.quantile(d, q)) * 1e-9 if d.size else None

    def digest(self) -> Dict[str, Dict[str, float]]:
        """Per span name, over the ring: ``count``, ``p50_ms``, ``p95_ms``,
        ``max_ms`` (what the server's ``stats`` op returns as ``spans``)."""
        out = {}
        for name in list(self.names):
            d = self.durations_ns(name)
            if d.size:
                p50, p95 = np.quantile(d, (0.5, 0.95)) * 1e-6
                out[name] = {"count": int(d.size), "p50_ms": float(p50), "p95_ms": float(p95),
                             "max_ms": float(d.max()) * 1e-6}
        return out

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Lifetime histogram digests, keyed by span name."""
        with self._lock:
            return {name: h.summary() for name, h in self.hist.items()}


# The process-wide recorder: servers and engines record here unless the
# server is given another.  A served tick leaves about ten spans, so 2^18
# events hold the last ~26,000 ticks: 7 minutes of a job ticking 60 times
# a second, and more at lower rates (older spans are counted in ``dropped``).
SPANS = SpanTimer(capacity=1 << 18)
