"""Unified telemetry (ISSUE 5): the observability layer every subsystem
reports through.

The reference's only observability is rank/epoch ``print``s and a
``time.clock()`` wall bracket (SURVEY.md §5); production pjit/TPU stacks
treat step-time breakdowns and per-request traces as first class
(arXiv:2204.06514 §5; the serving comparisons of arXiv:2605.25645 are
built entirely on such telemetry). Three pieces, one package:

- :mod:`ddl_tpu.obs.trace` — one span primitive with two sinks: a
  ``jax.profiler.TraceAnnotation`` (the span lands in the profiler's
  xplane, on the device timeline's clock) and a tracer's ``complete``
  (the JSONL :class:`Tracer`, convertible to a Chrome/Perfetto
  ``trace_event`` file, or anything of its three-member protocol);
  instant events go to the tracer alone. ``trace_context`` opens both
  sinks for one ``--trace-dir`` run.
- :mod:`ddl_tpu.obs.registry` — counters / gauges / histograms with
  label sets, a JSONL snapshot writer (manifest-first), and a
  Prometheus-text export. Replaces the ad-hoc per-subsystem stats
  dicts as the machine-readable surface (``ServeStats`` et al. remain
  as typed in-process views).
- :mod:`ddl_tpu.obs.health` — in-graph training health signals
  (global grad norm, per-subtree param/update norms, non-finite
  gradient counts) computed INSIDE the jitted step bodies as an aux
  output and fetched batched, so the hot path never gains a device
  sync.

The live SLO control plane (ISSUE 10) adds four more:

- :mod:`ddl_tpu.obs.slo` — streaming multi-window burn-rate monitors
  (``SloRule``/``SloMonitor``) evaluated per scheduler/router tick
  against the registry, emitting ``slo_burn_rate`` gauges,
  ``slo_alerts_total`` counters and ``slo_alert`` trace events.
- :mod:`ddl_tpu.obs.cost` — exact analytic FLOPs for the LM/CNN train
  steps and per-token serve work (paged-aware), the device peak-FLOPs
  table, and the ``mfu()`` division behind the ``train_mfu`` /
  ``serve_mfu`` gauges.
- :mod:`ddl_tpu.obs.memory` — device memory watermark gauges (guarded
  ``memory_stats()``) and the ``xla_compiles_total`` compile-activity
  counter every trainer/engine program build feeds.
- :mod:`ddl_tpu.obs.export` — the stdlib-threaded ``/metrics`` +
  ``/healthz`` HTTP pull endpoint behind CLI ``--prom-port``.

The goodput & time-attribution plane (ISSUE 11) adds three more:

- :mod:`ddl_tpu.obs.goodput` — per-span/per-tick wall-clock phase
  attribution (``GoodputTracker``): every observed second lands in
  exactly one phase, published as ``time_in_seconds{phase=}`` +
  ``goodput_fraction`` gauges next to the MFU story, with the pinned
  identity that phases sum to the observed wall time.
- :mod:`ddl_tpu.obs.anomaly` — streaming robust baselines
  (``AnomalyDetector``): rolling median/MAD per signal on the
  deterministic tick clock, edge-triggered ``anomaly`` trace events
  and ``anomaly_total{signal=}`` counters.
- :mod:`ddl_tpu.obs.analyze` — the offline CLI
  (``python -m ddl_tpu.obs.analyze``): goodput report, per-request
  critical-path breakdown and straggler/anomaly tables from a trace
  JSONL, plus a ``compare`` regression gate over two metrics
  artifacts (exit nonzero past a threshold).

The communication plane (ISSUE 20) adds one more:

- :mod:`ddl_tpu.obs.comms` — the collective-op HLO parser as a library
  surface (``benchmarks/collective_bytes.py`` now imports it), the
  per-program static collective ledger (``collective_bytes{kind=,
  program=}`` / ``collective_axis_bytes{axis=}`` /
  ``collective_ops_total``) published at the same build points
  ``xla_compiles_total`` counts, the per-device-kind ICI bandwidth
  table behind ``--ici-bw``, the two-roofline step-time model
  (``comms_time_model_s`` / ``comms_fraction`` /
  ``step_bound{bound=}`` next to ``train_mfu``) with its
  ``fit_roofline`` falsification harness, and the host byte plane
  (``handoff_bytes_total{path=}`` priced by ``serve.cache.
  kv_row_bytes``). ``analyze comms`` renders either a metrics JSONL or
  the bench artifact (README "Communication accounting").

Everything is surfaced by ``cli.py`` via ``--metrics-out``,
``--metrics-interval``, ``--trace-dir``, ``--prom-port``,
``--peak-flops``, ``--ici-bw``, ``--slo-rules`` and
``--anomaly-rules`` (README "Observability").
"""

from .registry import (  # noqa: F401
    MetricRegistry,
    MetricsWriter,
    NoSamplesError,
    run_manifest,
)
from .trace import NULL_TRACER, Tracer, trace_context  # noqa: F401
