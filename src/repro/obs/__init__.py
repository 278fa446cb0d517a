"""Unified run-telemetry layer: probes, spans, metrics, health, gating.

Five parts, all riding the existing engine/sweep/scenario/serving
machinery (DESIGN.md §9, §13):

* in-graph probes — a frozen `TraceConfig` selects cheap scalar
  diagnostics (drift/grad/residual/loss norms) that an algorithm's
  ``probe_round`` emits as extra ``lax.scan`` outputs; the engine
  assembles them into a `RunTrace` on ``FLResult.trace``. Probes-off is
  the default and leaves the compiled program untouched.
* in-graph health monitors (`repro.obs.health`) — nonfinite/explosion
  detectors riding the same scan-output contract, assembled into a
  `HealthReport` on ``FLResult.health``, with opt-in fail-fast raising
  `HealthError` naming the first bad round.
* host-side spans (`repro.obs.spans`) — nested wall-clock intervals
  (build/dispatch/eval, store export, the serve step's
  put/dispatch/tiers, collector passes ``gc.gen<N>``) with two sinks:
  Chrome-trace-event JSON in the run's trace dir, and the
  ``jax.profiler`` trace, where each span is a host event named
  ``repro.<name>`` on the device trace's clock. The device side of the
  same phases carries ``jax.named_scope`` names (``serve.gather``,
  ``store.scatter``, ``engine.eval``, ``permfl.grad``, ...) in its op
  metadata.
* metrics (`repro.obs.metrics`) — a counter/gauge/histogram registry
  with JSONL + Prometheus-text export; the serving path publishes LRU
  hit/miss, per-tier fallback counts, and replay latency into it.
* structured run events — one JSONL schema (`repro.obs.events`) written
  by ``run_experiment(trace_dir=...)`` / ``run_sweep`` / the scenarios
  CLI, read back by ``python -m repro.obs summarize``; ``python -m
  repro.obs report DIR`` joins events × spans × metrics × health.
* profiling + regression hooks — ``cost_analysis`` / ``jax.profiler``
  capture behind `TraceConfig`, and the `repro.obs.regress` comparator
  CI uses to gate ``BENCH_*.json`` against committed baselines.
"""
from repro.obs.events import (read_jsonl, run_events, summarize_run,
                              sweep_events, write_jsonl, write_run,
                              write_sweep)
from repro.obs.health import HealthError, HealthReport, nonfinite_count
from repro.obs.metrics import MetricsRegistry
from repro.obs.profiling import compiled_cost, profile_ctx
from repro.obs.regress import compare as compare_bench
from repro.obs.report import report_text
from repro.obs.spans import SpanLog, current_log, span
from repro.obs.trace import RunTrace, TraceConfig, eval_points

__all__ = ["HealthError", "HealthReport", "MetricsRegistry", "RunTrace",
           "SpanLog", "TraceConfig", "compare_bench", "compiled_cost",
           "current_log", "eval_points", "nonfinite_count",
           "profile_ctx", "read_jsonl", "report_text", "run_events",
           "span", "summarize_run", "sweep_events", "write_jsonl",
           "write_run", "write_sweep"]
