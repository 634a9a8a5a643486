"""Async device infeed + deferred telemetry for program host loops.

Re-designs the reference's L2 input machinery (`CreateTpuEnqueueOps`,
`base_input_generator.py:446`): there, host->device enqueue is double-buffered
against device dequeue so the accelerator never waits on input, and outfeed /
summary fetch runs on separate threads. In the JAX stack the device loop is a
jitted program fed by `device_put` batches, so the equivalent overlap is:

- `DeviceInfeed`: ONE background producer thread pulls host batches from the
  input generator (and optionally places them under the input sharding) into
  a bounded FIFO queue while the device computes the previous loop. A single
  producer + FIFO means the consumed batch sequence is bit-identical to
  calling the generator inline.
- `DeferredTelemetry`: ONE background worker runs the post-loop
  `device_get` of metrics/stats and the summary writes, so host fetch never
  sits between two device loops. Jobs run in submission order (single
  worker), keeping summaries ordered and the step-rate tracker monotone.

Producer/worker exceptions are latched and re-raised at the consumer
(`Get()` / `Future.result()`), so the train loop — and the executor's
transient-retry path above it — sees the real error instead of a silent
end-of-data or a dropped summary.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Iterator

_EOS = object()  # end-of-stream sentinel (never a valid batch)

# Producer threads that outlived their Stop() join (blocked inside the input
# generator), keyed by input stream: a NEW producer over the same stream —
# including one from a fresh DeviceInfeed instance (eval creates a throwaway
# infeed per Run) — must wait these out or fail loudly rather than race the
# generator and corrupt batch order.
_LINGERING_LOCK = threading.Lock()
_LINGERING: dict[Any, threading.Thread] = {}

# One-shot multi-host producer-placement probe verdict (see
# ProbeProducerPlacement). Cached per process: the answer is a property of
# the runtime/backend pairing, not of any one program.
_PROBE_LOCK = threading.Lock()
_PROBE_VERDICT: bool | None = None


def _DefaultPlacementProbe() -> None:
  """Representative off-main-thread `make_array_from_process_local_data`
  call: a tiny replicated array over every device. Raises (or hangs) on
  runtimes where the collective array build is not thread-safe off the
  main thread."""
  import jax
  import numpy as np
  devs = np.asarray(jax.devices())
  mesh = jax.sharding.Mesh(devs.reshape(-1), ("probe",))
  sharding = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
  arr = jax.make_array_from_process_local_data(
      sharding, np.zeros((1,), np.float32), (1,))
  jax.block_until_ready(arr)


def ProbeProducerPlacement(probe_fn: Callable[[], None] | None = None,
                           timeout_s: float = 20.0) -> bool:
  """One-shot safety probe: may H2D placement run on the producer thread
  under real multi-host?

  Producer-side placement overlaps the H2D transfer with compute, but
  `jax.make_array_from_process_local_data` builds a *global* array and some
  runtime versions only support that from the main thread. Rather than
  hard-coding the conservative consumer-side fallback forever, run ONE
  representative call on a scratch thread with a join timeout; any
  exception or hang means "not safe". Multi-process, the verdict is
  all-reduced (process_allgather on the calling thread) so every host makes
  the same producer-vs-consumer placement choice — hosts disagreeing would
  skew per-host infeed latency and, worse, diverge any placement-dependent
  collective setup.

  The default probe's verdict is cached for the process; an injected
  `probe_fn` (tests) bypasses the cache.
  """
  global _PROBE_VERDICT
  import jax
  with _PROBE_LOCK:
    if probe_fn is None and _PROBE_VERDICT is not None:
      return _PROBE_VERDICT
    ok = [False]

    def _Run():
      try:
        (probe_fn or _DefaultPlacementProbe)()
        ok[0] = True
      except BaseException:  # noqa: BLE001 - any failure means "not safe"
        ok[0] = False

    t = threading.Thread(target=_Run, daemon=True, name="placement-probe")
    t.start()
    t.join(timeout_s)
    verdict = bool(ok[0]) and not t.is_alive()
    if jax.process_count() > 1:
      try:
        import numpy as np
        from jax.experimental import multihost_utils
        verdicts = multihost_utils.process_allgather(np.asarray([verdict]))
        verdict = bool(np.all(verdicts))
      except BaseException:  # noqa: BLE001 - coordination failure: fall back
        verdict = False
    if probe_fn is None:
      _PROBE_VERDICT = verdict
    return verdict


class DeviceInfeed:
  """Bounded background producer queue feeding device (or host) batches.

  Args:
    make_iter: callable returning a FRESH iterator of host batches; invoked
      once per producer start (and again after `Reset`).
    place_fn: optional host->device placement applied per batch.
    depth: queue capacity (loop batches for on-device loops, single batches
      for per-step loops) — bounds host memory while the device lags.
    place_in_producer: apply `place_fn` on the producer thread so the H2D
      transfer overlaps compute too. False hands numpy to the consumer,
      which must place — the verified-safe multi-process variant, keeping
      `make_array_from_process_local_data` on the consumer thread.
    name: thread-name prefix for debugging.
    stream_key: identity of the underlying input stream (e.g.
      `id(generator)`). Serializes producers across DeviceInfeed
      *instances* sharing one stream — see _LINGERING. Defaults to this
      instance (per-instance protection only).
    registry: optional observe.MetricsRegistry — registers an
      `infeed/<name>` section (wait_s / batches / queue_depth / healthy)
      so every live infeed is visible in one snapshot; re-registering
      under the same name replaces the section (throwaway eval infeeds).

  Batch ORDER is the iterator's order: one producer thread and one FIFO
  queue, so the consumed sequence is bit-identical to the synchronous path.
  """

  def __init__(self, make_iter: Callable[[], Iterator[Any]],
               place_fn: Callable[[Any], Any] | None = None,
               depth: int = 2, place_in_producer: bool = True,
               name: str = "infeed", stream_key: Any = None,
               registry: Any = None):
    self._stream_key = stream_key if stream_key is not None else id(self)
    self._make_iter = make_iter
    self._place_fn = place_fn
    self._depth = max(1, int(depth))
    self._place_in_producer = bool(place_in_producer and
                                   place_fn is not None)
    self._name = name
    self._thread: threading.Thread | None = None
    self._queue: "queue.Queue" | None = None
    self._stop: threading.Event | None = None
    self._error: BaseException | None = None
    self._done = False
    self.wait_s = 0.0  # cumulative consumer blocking time (starvation)
    self.batches = 0   # batches handed to the consumer
    if registry is not None:
      registry.SectionFn(f"infeed/{name}", self.Stats)

  def Stats(self) -> dict:
    """Live counters for the registry's `infeed/<name>` section."""
    return {
        "wait_s": self.wait_s,
        "batches": self.batches,
        "queue_depth": self.QueueDepth(),
        "healthy": self.healthy,
    }

  @property
  def places_batches(self) -> bool:
    """True when Get() returns device-placed batches (skip _PutBatch)."""
    return self._place_in_producer

  @property
  def healthy(self) -> bool:
    return self._error is None

  def QueueDepth(self) -> int:
    q = self._queue
    return q.qsize() if q is not None else 0

  def _EnsureStarted(self) -> None:
    if self._thread is not None or self._done:
      return
    with _LINGERING_LOCK:
      lingering = _LINGERING.pop(self._stream_key, None)
    if lingering is not None and lingering.is_alive():
      # a previous Stop() (possibly on a DISCARDED DeviceInfeed over the
      # same stream) timed out while its producer was blocked inside the
      # generator; two producers pulling one generator would race and
      # break batch order — wait it out (it parks after its current pull)
      # or fail loudly rather than corrupt the stream
      lingering.join(timeout=30.0)
      if lingering.is_alive():
        with _LINGERING_LOCK:
          _LINGERING[self._stream_key] = lingering
        raise RuntimeError(
            f"{self._name}: previous producer thread is still blocked in "
            "the input generator; refusing to start a second producer "
            "over the same stream")
    self._queue = queue.Queue(maxsize=self._depth)
    self._stop = threading.Event()
    self._thread = threading.Thread(
        target=self._Produce, args=(self._queue, self._stop),
        name=f"{self._name}-producer", daemon=True)
    self._thread.start()

  def _Produce(self, q: "queue.Queue", stop: threading.Event) -> None:
    # q/stop passed as args (not read from self): a Reset() from the
    # consumer swaps the members, and an abandoned producer must keep
    # honoring ITS stop event rather than the replacement's.
    # spans: jax.profiler.TraceAnnotation on this thread's line of a
    # profiler trace (a flag test when none is running); the time spent
    # parked on the full queue lies under neither
    import jax
    try:
      it = iter(self._make_iter())
      while True:
        with jax.profiler.TraceAnnotation("lingvo/infeed/produce"):
          item = next(it, _EOS)
        if item is _EOS:
          break
        if self._place_in_producer:
          with jax.profiler.TraceAnnotation("lingvo/infeed/place"):
            item = self._place_fn(item)
        while not stop.is_set():
          try:
            q.put(item, timeout=0.2)
            break
          except queue.Full:
            continue
        if stop.is_set():
          return
    except BaseException as e:  # noqa: BLE001 - surfaced at Get()
      if not stop.is_set():
        # a stopped producer's late exception must not poison the latch a
        # Reset() just cleared for the NEXT epoch
        self._error = e
    finally:
      while not stop.is_set():
        try:
          q.put(_EOS, timeout=0.2)
          return
        except queue.Full:
          continue

  def Get(self) -> Any | None:
    """Next batch, or None at end-of-stream (latched).

    Re-raises a producer exception (also latched: a dead producer must not
    masquerade as end-of-data). Blocking time accumulates in `wait_s`.
    """
    self._EnsureStarted()
    if self._done:
      if self._error is not None:
        raise self._error
      return None
    t0 = time.perf_counter()
    item = self._queue.get()
    self.wait_s += time.perf_counter() - t0
    if item is _EOS:
      self._done = True
      if self._error is not None:
        raise self._error
      return None
    self.batches += 1
    return item

  def Iter(self) -> Iterator[Any]:
    """Generator view over Get() (finite-stream consumers, e.g. eval)."""
    while True:
      item = self.Get()
      if item is None:
        return
      yield item

  def Stop(self) -> None:
    """Stops the producer and discards queued batches. Safe to call twice."""
    thread, q, stop = self._thread, self._queue, self._stop
    self._thread = None
    self._queue = None
    self._stop = None
    if stop is not None:
      stop.set()
    if q is not None:
      try:
        while True:
          q.get_nowait()
      except queue.Empty:
        pass
    if thread is not None:
      # The producer may be blocked inside the generator itself (e.g. an
      # upstream prefetcher); it is a daemon and parks after its current
      # pull, so don't hang the trainer on it here — but remember it, so a
      # restart can't race it on the same generator (_EnsureStarted).
      thread.join(timeout=5.0)
      if thread.is_alive():
        with _LINGERING_LOCK:
          _LINGERING[self._stream_key] = thread

  def Reset(self) -> None:
    """Stop + clear latched end/error state; the next Get() starts a fresh
    `make_iter()` iterator. Prefetched-but-unconsumed batches are discarded
    (callers resetting the underlying generator get a consistent restart)."""
    self.Stop()
    self._done = False
    self._error = None


class DeferredTelemetry:
  """Single-worker executor for post-loop metric fetch + summary writes.

  One worker => jobs complete in submission order. The consumer bounds the
  in-flight window (`TrainProgram.Run` keeps at most `pipeline_depth`
  unresolved loops, one for the legacy `pipeline_depth=0` path), so
  results the executor consumes — NaN-stop, trial reporting, early-stop —
  lag dispatch by at most that many loops (docs/pipelined_executor.md).
  """

  def __init__(self, name: str = "telemetry"):
    self._name = name
    self._pool: ThreadPoolExecutor | None = None

  def Submit(self, fn: Callable[[], Any]) -> Future:
    if self._pool is None:
      self._pool = ThreadPoolExecutor(max_workers=1,
                                      thread_name_prefix=self._name)
    return self._pool.submit(fn)

  def Shutdown(self) -> None:
    """Waits for in-flight jobs; the next Submit() lazily restarts."""
    pool, self._pool = self._pool, None
    if pool is not None:
      pool.shutdown(wait=True)
