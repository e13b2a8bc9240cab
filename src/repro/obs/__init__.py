"""Unified observability: device telemetry, merged traces, metrics export,
and the closed profiling loop (measure -> calibrate -> detect drift).

Coordinated surfaces (DESIGN.md §8-§9):

- ``obs.telemetry``   the carry-threaded ``StageTelemetry`` pytree charged
                      per (stage, tick) inside the jitted pipeline scan —
                      pool occupancy, resident KV bytes, spill/fetch/qship
                      event counts, attention work units, launch counts.
                      Returned by ``prefill_pipeline(...,
                      return_telemetry=True)`` as ``[N, T]`` profiles.
- ``obs.trace``       the Chrome/Perfetto trace recorder: scheduler task
                      spans + engine wave/tick spans + per-stage counter
                      tracks, one merged file (atomic export).
- ``obs.metrics``     counters/gauges/histograms with JSON-lines and
                      Prometheus-textfile export for serving runs.
- ``obs.calibrate``   least-squares fit of the ``HardwareProfile`` effective
                      rates against ``[N, T]`` tick spans; calibrated-profile JSON
                      accepted by ``lbcp.plan_partition`` /
                      ``chunk_cost_arrays`` / scheduler admission.
- ``obs.health``      runtime sentinels: non-finite activations, telemetry
                      vs analytic drift, SLO burn-rate — one structured
                      alert stream into metrics + trace.

``obs.trace`` / ``obs.metrics`` / ``obs.health`` / ``obs.calibrate`` are
import-light (stdlib/numpy) so scheduler and benchmark code can depend on
them; ``obs.telemetry`` pulls in jax and is imported only by ``repro.core``
and engine code (``health`` reaches jax lazily, inside methods).
"""
from repro.obs.health import Alert, HealthMonitor, slo_burn_rate
from repro.obs.metrics import MetricsRegistry, export_engine_metrics
from repro.obs.trace import TraceRecorder

__all__ = ["Alert", "HealthMonitor", "MetricsRegistry", "TraceRecorder",
           "export_engine_metrics", "slo_burn_rate"]
