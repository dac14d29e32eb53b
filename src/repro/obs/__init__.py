"""repro.obs — the metrics spine.

Three layers, one schema:

* :mod:`repro.obs.taps` — in-scan metric taps (typed counter/gauge
  registry, windowed aggregates) the ``RoundProgram`` taps stage emits.
* :mod:`repro.obs.runlog` — schema-versioned JSONL run logs every runner,
  grid and serving loop writes through.
* :mod:`repro.obs.trace` — stage-level trace annotations for device code
  and the host span recorder (``SpanTimer``, the process-wide ``SPANS``).

* :mod:`repro.obs.sketches` — fixed-size mergeable client-axis sketches
  (count/probability/lag histograms, per-region rollups) carried in the
  scan, plus the fairness series derived from them.
* :mod:`repro.obs.alerts` — rule-based outage/starvation/drift detection
  over tap + sketch streams, appended to run logs as ``alert`` events.

plus :mod:`repro.obs.paths` (one results layout) and
:mod:`repro.obs.report` (the unified Reporter benchmarks emit through).

This package must stay importable without the engine: it imports only
numpy / stdlib at module scope (jax lazily), so ``repro.engine`` can
depend on it without cycles.
"""
from .alerts import Alert, AlertRules, detect_alerts, log_alerts
from .paths import artifact_path, bench_dir, bench_path, results_root, runlog_dir, runlog_path
from .report import Reporter
from .runlog import SCHEMA_VERSION, RunLog, iter_alerts, iter_metrics, read_runlog, validate_records
from .sketches import SKETCH_FIELDS, SketchSpec, fairness_series, merge_sketches, sketch_from_dense
from .taps import ROUND_TAPS, TapRegistry, TapSpec, window_reduce
from .trace import SPANS, LatencyHistogram, SpanTimer, stage

__all__ = [
    "artifact_path", "bench_dir", "bench_path", "results_root", "runlog_dir", "runlog_path",
    "Reporter",
    "SCHEMA_VERSION", "RunLog", "read_runlog", "validate_records", "iter_metrics", "iter_alerts",
    "SKETCH_FIELDS", "SketchSpec", "fairness_series", "merge_sketches", "sketch_from_dense",
    "Alert", "AlertRules", "detect_alerts", "log_alerts",
    "ROUND_TAPS", "TapRegistry", "TapSpec", "window_reduce",
    "LatencyHistogram", "SpanTimer", "SPANS", "stage",
]
