"""Unified telemetry spine (docs/observability.md).

Three parts:

- :mod:`.trace` — span tracer: Chrome-trace JSON (Perfetto-loadable)
  plus an append-only, versioned JSONL event log; near-zero overhead
  when disabled;
- :mod:`.metrics` — process-local counter/gauge/histogram registry,
  snapshotted to JSON or Prometheus text format;
- :mod:`.device` — the one door through which the host reads a device
  array (timed, counted), and phase spans that split their wall clock
  into CPU, device waits and the rest.

All are stdlib-only imports (no jax, no engine) so backend-free front
ends — ``campaign-merge``, bench's pre-probe phase, the trace report
tool — can load them without initializing a backend.
"""

from . import device, metrics, trace

__all__ = ["device", "metrics", "trace"]
