"""The device side of a run: which chips, the compile cache, compile seconds,
peak bytes, and the fence that closes a timing."""

from __future__ import annotations

import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class NoDevice(RuntimeError):
  pass


def Require(chips: int, rehearse: bool) -> dict:
  """The contract's `device` object, or NoDevice. A measured run needs a TPU
  and at least the chips the cell asks for; only the rehearsal takes a CPU."""
  import jax
  devices = jax.devices()
  device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
  if not rehearse and device["platform"] != "tpu":
    raise NoDevice(f"no TPU: JAX found {device}")
  if device["count"] < chips:
    raise NoDevice(f"the cell needs {chips} chips: JAX found {device}")
  return device


def ConfigureCache() -> str:
  """JAX's persistent compilation cache at the path JAX_COMPILATION_CACHE_DIR
  gives, else at the fixed <checkout>/.jax_cache (the program's own rule,
  lingvo_tpu/core/compile_cache.py). Every program is cached, however quick
  its compile was, so that a second run finds all of them."""
  import jax
  from lingvo_tpu.core import compile_cache
  path = compile_cache.Configure()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
  return path


class CompileClock:
  """Sums what JAX reports it spent tracing, lowering and compiling (or
  fetching from the cache), counts cache hits and misses, and keeps each
  event with the time it was reported at (time.perf_counter), so that a run
  can say which of them fell inside its window."""

  def __init__(self):
    import jax
    self.events: list[tuple[float, str, float]] = []   # (at, event, seconds)
    self.seconds = 0.0
    self.hits = 0
    self.misses = 0
    jax.monitoring.register_event_duration_secs_listener(self._OnDuration)
    jax.monitoring.register_event_listener(self._OnEvent)

  def _OnDuration(self, event: str, secs: float, **_) -> None:
    if event in _COMPILE_EVENTS:
      self.seconds += secs
      self.events.append((time.perf_counter(), event.rsplit("/", 1)[-1], secs))

  def _OnEvent(self, event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
      self.hits += 1
    elif event == "/jax/compilation_cache/cache_misses":
      self.misses += 1


def MemoryPeakBytes(chips: int) -> int:
  """Peak bytes in use on the fullest of the chips used (0 on a CPU)."""
  import jax
  peak = 0
  for d in jax.devices()[:chips]:
    stats = d.memory_stats() or {}
    peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
  return peak


def Fence(tree) -> None:
  """block_until_ready on every leaf: the one way a timing is closed (copied
  from bench._StepTime; JAX returns before the device has finished)."""
  import jax
  jax.block_until_ready(tree)
