"""Goodput/badput wall-time accounting + honest MFU publication.

Two questions a fleet dashboard asks of every trainer that this module
answers from the process-global registry:

- **Where did the wall time go?** `GoodputTracker` classifies elapsed
  time into the `schema.GOODPUT_BUCKETS`: `step` (productive device
  loops) vs badput — `compile`, `checkpoint_save`/`_restore`, `eval`,
  `infeed_wait`, `recovery` (transient-failure retries) — plus the
  residual `other` (wall − accounted), so the buckets always sum to
  ~wall time. Hooks are context managers (`with tracker.Track("eval")`)
  placed in the train/eval programs and the executor; the tracker
  publishes everything as a lazy `goodput/*` registry section, so the
  numbers are current at every scrape without a publish step.

  Under the PIPELINED executor (TrainProgram.pipeline_depth >= 1) the
  train attribution moves from Run-wall windows to loop-COMPLETION
  intervals (`_AttributePipelinedLoop`): device loops execute serially
  however far ahead the host dispatches, so completion-to-completion
  spans partition the wall; each span minus the infeed wait and compile
  seconds that accrued inside it lands in `step`. `checkpoint_save` then
  counts only the caller-side snapshot fence of an ACTUAL async write —
  a cadence no-op contributes zero — so a shrinking `other_s` +
  `checkpoint_save_s` against a fixed workload is exactly the badput the
  pipeline reclaimed (docs/pipelined_executor.md).

- **How fast relative to the hardware?** `PublishMfu` wires a
  `train/mfu` lazy gauge: the train-step executable's XLA cost analysis
  (flops/step, recorded by the programs' CompileLog/_RecordCompile or a
  lazy `.lower().cost_analysis()` — no second compile either way) × the
  `StepRateTracker` step-rate gauge ÷ nominal peak FLOP/s of the
  attached devices. Peak numbers are per-chip dense-matmul nominals; on
  CPU the denominator is a placeholder, so treat CPU MFU as relative
  only (the flops numerator and the published `train/flops_per_step`
  are exact everywhere).
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax

from lingvo_tpu.observe import schema

# Nominal peak dense-matmul FLOP/s per chip by device-kind substring
# (bf16 numbers for TPUs). Matched case-insensitively, first hit wins;
# order newest-first so "v5p" matches before "v5".
PEAK_FLOPS_BY_KIND = (
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
    ("cpu", 1e11),   # placeholder: CPU MFU is relative, not absolute
)
DEFAULT_PEAK_FLOPS = 100e12


def PeakFlopsPerDevice(device_kind: str | None = None) -> float:
  """Nominal per-chip peak FLOP/s for a device kind (default: device 0)."""
  if device_kind is None:
    devs = jax.devices()
    device_kind = devs[0].device_kind if devs else ""
  kind = (device_kind or "").lower()
  for sub, peak in PEAK_FLOPS_BY_KIND:
    if sub in kind:
      return peak
  return DEFAULT_PEAK_FLOPS


class GoodputTracker:
  """Accumulates wall time into goodput/badput buckets (module docstring).

  clock: injectable monotonic-seconds source (tests). Registering with a
  registry publishes `Stats()` as the lazy `goodput/*` section. One
  tracker per process is the normal shape (`Get()`); programs and the
  executor all feed the same one so buckets partition ONE wall clock.
  """

  def __init__(self, registry=None, clock=time.perf_counter,
               section: str = "goodput", compile_record=None):
    self._clock = clock
    self._lock = threading.Lock()
    self._t0 = clock()
    self._buckets = {b: 0.0 for b in schema.GOODPUT_BUCKETS if b != "other"}
    # compile seconds by the thread that compiled (CompileSeconds)
    self._compile_by_thread: dict[int, float] = {}
    # the record whose compile events (self seconds, so a nested event
    # counts once) are this tracker's `compile` bucket beside what callers
    # Add: observe.profile's start-up record for the process's tracker
    # (`Get()`), none for a tracker of a test's own. The marks are the
    # record's seconds at the last Reset.
    self._record = compile_record
    self._MarkRecord()
    if registry is not None:
      registry.SectionFn(section, self.Stats)

  def _MarkRecord(self):
    rec = self._record
    self._record_mark = rec.TotalCompileSeconds() if rec else 0.0
    self._record_thread_marks = rec.CompileSecondsByThread() if rec else {}

  def _RecordSeconds(self) -> float:
    if self._record is None:
      return 0.0
    return max(self._record.TotalCompileSeconds() - self._record_mark, 0.0)

  def Add(self, bucket: str, seconds: float):
    assert bucket in self._buckets, (
        f"unknown goodput bucket {bucket!r}; schema.GOODPUT_BUCKETS = "
        f"{schema.GOODPUT_BUCKETS}")
    seconds = max(float(seconds), 0.0)
    with self._lock:
      self._buckets[bucket] += seconds
      if bucket == "compile":
        ident = threading.get_ident()
        self._compile_by_thread[ident] = (
            self._compile_by_thread.get(ident, 0.0) + seconds)

  def CompileSeconds(self, thread: int | None = None) -> float:
    """Monotonic compile seconds spent ON one thread (`thread`: its
    threading.get_ident(); default the caller's) — callers snapshot it
    around a window to find how much compilation delayed that window. A
    compile on another thread (the async checkpoint writer, a scrape)
    runs beside the window's work and takes nothing from it: summed in,
    two threads compiling at once exceed the wall and zero the window."""
    if thread is None:
      thread = threading.get_ident()
    with self._lock:
      added = self._compile_by_thread.get(thread, 0.0)
      if self._record is None:
        return added
      return added + max(self._record.CompileSeconds(thread)
                         - self._record_thread_marks.get(thread, 0.0), 0.0)

  def Snapshot(self) -> dict:
    """Raw bucket totals {bucket: seconds} at this instant — a cheap
    before/after basis for windowed deltas (bench sections, tests)
    without the wall/residual derivation Stats() adds."""
    with self._lock:
      out = dict(self._buckets)
    out["compile"] += self._RecordSeconds()
    return out

  @contextlib.contextmanager
  def Track(self, bucket: str):
    """Attributes the wall time of the enclosed block to `bucket`."""
    t0 = self._clock()
    try:
      yield
    finally:
      self.Add(bucket, self._clock() - t0)

  @contextlib.contextmanager
  def TrackExcludingCompile(self, bucket: str):
    """Like Track, minus any compile seconds the start-up record's listener
    attributed to this thread during the block — lazy jit compiles inside
    a step/eval window must not be double-counted as productive (or eval)
    time."""
    t0 = self._clock()
    c0 = self.CompileSeconds()
    try:
      yield
    finally:
      elapsed = self._clock() - t0
      compiled = self.CompileSeconds() - c0
      self.Add(bucket, max(elapsed - compiled, 0.0))

  def Reset(self):
    with self._lock:
      self._t0 = self._clock()
      for b in self._buckets:
        self._buckets[b] = 0.0
      self._compile_by_thread.clear()
      self._MarkRecord()

  def Stats(self) -> dict:
    """`goodput/*` section: per-bucket seconds + wall + productive ratio.
    `other_s` is the residual (clamped at 0), so the buckets sum to wall —
    up to what a side thread compiled beside a step (noted below)."""
    buckets = self.Snapshot()
    with self._lock:
      wall = max(self._clock() - self._t0, 0.0)
      out = {f"{b}_s": round(v, 6) for b, v in buckets.items()}
      accounted = sum(buckets.values())
      productive = sum(buckets[b] for b in schema.GOODPUT_PRODUCTIVE)
    out["other_s"] = round(max(wall - accounted, 0.0), 6)
    out["wall_s"] = round(wall, 6)
    out["productive_ratio"] = round(productive / wall, 6) if wall else 0.0
    assert set(out) == set(schema.GOODPUT_STATS_KEYS)
    return out


_GET_LOCK = threading.Lock()
_TRACKER: GoodputTracker | None = None

# The compile bucket of the process's tracker is fed by observe.profile's
# start-up record, the program's one jax.monitoring listener: every trace,
# lowering and backend compile (or cache fetch), AOT or lazy, with an event
# nested in another of its thread counted for its self time only. What a
# side thread compiles beside a running step still counts in full, so the
# buckets sum to ~wall, not exactly wall. This is how lazily-jitted programs
# (no AOT CompileLog) land their compile wall in the compile bucket instead
# of hiding inside a step window.


def Get() -> GoodputTracker:
  """The process-global tracker, registered on observe.Default()."""
  global _TRACKER
  with _GET_LOCK:
    if _TRACKER is None:
      from lingvo_tpu.observe import metrics as metrics_lib
      from lingvo_tpu.observe import profile as profile_lib
      _TRACKER = GoodputTracker(registry=metrics_lib.Default(),
                                compile_record=profile_lib.Startup())
    return _TRACKER


def PublishMfu(registry, flops_per_step: float,
               rate_gauge: str = "train/train_steps_per_second",
               name: str = "train/mfu",
               peak_flops: float | None = None):
  """Wires `train/mfu` as a lazy gauge over the step-rate gauge.

  mfu = flops_per_step × steps_per_second / (per-device peak × #devices).
  Reading the rate gauge's `.value` inside the GaugeFn is safe: the
  registry lock is an RLock and the snapshot already holds it. Also
  publishes the inputs (`train/flops_per_step`, `train/peak_flops`) so a
  scraper can recompute with its own peak numbers."""
  if peak_flops is None:
    peak_flops = PeakFlopsPerDevice() * max(jax.device_count(), 1)
  flops = float(flops_per_step)
  registry.Gauge("train/flops_per_step").Set(flops)
  registry.Gauge("train/peak_flops").Set(float(peak_flops))
  rate_g = registry.Gauge(rate_gauge)

  def _Mfu():
    rate = rate_g.value
    if not isinstance(rate, (int, float)) or rate <= 0 or peak_flops <= 0:
      return 0.0
    return flops * rate / peak_flops

  registry.GaugeFn(name, _Mfu)
