"""lingvo_tpu.observe: the framework-wide observability layer.

The in-process pillars (ISSUE 12), one import:

- `MetricsRegistry` / `Default()` (observe/metrics.py): counters, gauges,
  histograms with atomic snapshots and monotonic-delta semantics. Serving
  engines own per-instance registries; train/eval programs and infeeds
  publish to the process-global default.
- `TraceRecorder` (observe/trace.py): per-request serving lifecycle traces
  in a lock-cheap ring buffer, derived per-request metrics, and Chrome
  trace-event JSON export (Perfetto-openable; one row per decode slot).
- `ProfileWindow` / `CompileLog` (observe/profile.py): on-demand
  jax.profiler trace windows (no-op when unsupported) and one-shot
  per-compiled-program records (compile wall time and what it was made
  of, XLA memory plan, donation set); `Startup()`, the process's record of
  its own set-up: phases, named programs, every compile event JAX reports.

And the fleet-facing layer (ISSUE 13) on top:

- `StatusServer` / `PrometheusText` (observe/export.py): a stdlib HTTP
  thread per process serving /metrics, /statusz, /traces, /healthz.
- `GoodputTracker` / `PublishMfu` (observe/goodput.py): wall-time
  goodput/badput buckets + the `train/mfu` lazy gauge.
- `StallWatchdog` (observe/watchdog.py): heartbeat liveness, stall trip
  taxonomy, automatic ProfileWindow flight capture.
- `observe.aggregate`: scrape-and-merge across N replica endpoints.

`observe.schema` declares every telemetry key set once — engine `Stats()`,
GShardDecode telemetry, endpoint paths, /statusz keys, goodput buckets and
watchdog stats are views generated from it, and its `DEVICE_SCOPES` is the
one tree of `jax.named_scope` names the jitted programs enter, each through
`observe.Scope(name)`.
"""

from lingvo_tpu.observe import aggregate  # noqa: F401
from lingvo_tpu.observe import schema  # noqa: F401
from lingvo_tpu.observe.export import (  # noqa: F401
    BuildInfo, MetricName, PrometheusText, StatusServer)
from lingvo_tpu.observe.goodput import (  # noqa: F401
    GoodputTracker, PeakFlopsPerDevice, PublishMfu)
from lingvo_tpu.observe.metrics import (  # noqa: F401
    DEFAULT_BOUNDS, Default, HistogramQuantiles, MetricsRegistry)
from lingvo_tpu.observe.profile import (  # noqa: F401
    CompileInfo, CompileLog, ProfileWindow, ProfilerSupported, Startup,
    StartupRecord)
from lingvo_tpu.observe.schema import Scope  # noqa: F401
from lingvo_tpu.observe.trace import (  # noqa: F401
    RequestTrace, TraceRecorder)
from lingvo_tpu.observe.watchdog import StallWatchdog  # noqa: F401
