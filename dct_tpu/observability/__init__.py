"""Platform-wide telemetry: the operator plane the reference lacks.

The reference platform's only observability is stdout prints scraped from
Airflow task logs (SURVEY §5.1). This package is the TPU-scale operator
plane built on four pillars:

- :mod:`events` — append-only structured JSONL event log with a
  run-correlation ID minted by the DAG/launcher and passed via env to
  every rank, so ONE grep reconstructs a whole continuous-training cycle
  (launch -> train -> checkpoint -> tracking -> deploy) across processes.
- :mod:`goodput` — wall-clock ledger classifying run time into
  train_step / eval / compile / checkpoint / data_wait /
  startup_recovery, the "what fraction of the run was productive?"
  accounting the pjit/TPUv4 training reports treat as first-class.
- :mod:`heartbeat` — per-rank liveness files + a launcher-side monitor
  that names stalled/dead/straggling ranks instead of waiting silently
  on join.
- :mod:`prometheus` — text-exposition (0.0.4) rendering for the serving
  server's ``GET /metrics`` and the trainer's end-of-run metrics dump.
- :mod:`spans` — cross-process distributed tracing: per-process span
  JSONL sharing the run-correlation ID as trace_id, parent spans
  propagated to children via ``DCT_SPAN_ID``.
- :mod:`trace_export` — deterministic merge of all ranks' span files
  into one Perfetto-loadable Chrome-trace-event ``trace.json``.
- :mod:`health` — training-health telemetry: NaN/Inf-loss guard,
  loss-spike and grad-norm z-score detectors, warn-or-halt policy.
- :mod:`inspect` — the run-inspector CLI
  (``python -m dct_tpu.observability.inspect <run_dir>``) joining
  events + spans + goodput + heartbeats into a cycle report.
- :mod:`metrics` / :mod:`aggregate` / :mod:`slo` — the metrics plane
  (ISSUE 8): a general registry (counter/gauge/histogram with merge
  semantics) every process publishes as atomic snapshot files, scrape-
  time aggregation into fleet totals + per-``proc`` series, and SLO
  burn-rate monitoring (``slo.alert`` events, ``dct_slo_*`` gauges)
  over the aggregated view.

Everything here is dependency-free, failure-isolated (a full disk or an
unwritable dir degrades telemetry to a no-op, never fails training), and
clock-injectable for tests.
"""

from dct_tpu.observability.events import (  # noqa: F401
    EventLog,
    current_run_id,
    event_log_from_config,
    get_default,
    mint_run_id,
    set_default,
)
from dct_tpu.observability.goodput import (  # noqa: F401
    CATEGORIES,
    GoodputLedger,
)
from dct_tpu.observability.heartbeat import (  # noqa: F401
    HeartbeatMonitor,
    HeartbeatWriter,
    RankStatus,
)
from dct_tpu.observability.prometheus import (  # noqa: F401
    LATENCY_BUCKETS,
    HistogramAccumulator,
    MetricFamily,
    render,
)
