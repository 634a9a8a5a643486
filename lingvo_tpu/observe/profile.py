"""Profiler windows + one-shot per-compiled-program records.

ISSUE 12 pillar 3, two tools:

- `ProfileWindow`: an on-demand `jax.profiler` trace window. Use as a
  context manager around a region (train loop Run), or arm it with
  `steps=N` and tick `StepDone()` from a step loop (the serving engine's
  `ProfileSteps`) so the trace covers exactly N engine steps. Every
  profiler call is guarded: on builds/backends without profiler support
  the window degrades to a no-op (`active` stays False) instead of
  raising — observability must never take the service down.

- `CompileLog`: ahead-of-time compiles a jitted callable ONCE per named
  program via `.lower(*args).compile()`, records compile wall time, the
  XLA memory analysis (temp/argument/output bytes — the static memory
  plan), and the donation set, then dispatches every subsequent call
  through the stored executable. The jit tracing cache does not see
  `.lower().compile()`, so the compiled object MUST be reused for
  dispatch or each call would pay tracing again. Any failure — lowering,
  memory_analysis, or an aval mismatch at dispatch — permanently falls
  back to calling the original jit fn for that name, recording why.
"""

from __future__ import annotations

import collections
import re
import time
from typing import Optional

import jax

# opcode of a collective instruction in optimized HLO text ("-start" is the
# async form of the same operation; its "-done" half is not counted again)
_COLLECTIVE_RE = re.compile(
    r"= [^=\n]*?\b(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter)(?:-start)?\(")


def ProfilerSupported() -> bool:
  return hasattr(jax, "profiler") and hasattr(jax.profiler, "start_trace")


class ProfileWindow:
  """A start/stop (or N-step) jax.profiler trace window; no-op when
  unsupported. Traces land under `<logdir>/plugins/profile/<ts>/` (the
  XProf/TensorBoard layout jax.profiler writes)."""

  def __init__(self, logdir: str, steps: int = 0):
    self.logdir = logdir
    self.steps_remaining = int(steps)
    self.active = False
    self.error: Optional[str] = None

  def Start(self):
    """Starts the trace (idempotent)."""
    if self.active or self.error is not None:
      return self
    try:
      jax.profiler.start_trace(self.logdir)
      self.active = True
    except Exception as e:  # noqa: BLE001 - degrade to no-op
      self.error = f"{type(e).__name__}: {e}"
    return self

  def Stop(self):
    """Stops the trace (idempotent)."""
    if not self.active:
      return
    self.active = False
    try:
      jax.profiler.stop_trace()
    except Exception as e:  # noqa: BLE001
      self.error = f"{type(e).__name__}: {e}"

  def StepDone(self) -> bool:
    """Ticks an armed N-step window; returns True when the window closed
    (caller should drop its reference)."""
    if self.error is not None:
      return True
    self.steps_remaining -= 1
    if self.steps_remaining <= 0:
      self.Stop()
      return True
    return False

  def __enter__(self):
    return self.Start()

  def __exit__(self, *exc):
    self.Stop()
    return False


def CompileInfo(compiled) -> dict:
  """XLA static-memory-plan facts of a Compiled object; every accessor is
  version-guarded (memory_analysis is unavailable on some backends). Plus
  two counts from the optimized HLO text: `tpu_custom_calls`, the Pallas
  kernels the program holds — 0 where one was expected means its XLA twin
  or interpret mode ran instead — and `collectives` by opcode, which says
  whether a sharded program really talks across devices."""
  text = compiled.as_text()
  info = {
      "tpu_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
      "collectives": dict(collections.Counter(_COLLECTIVE_RE.findall(text))),
  }
  try:
    ma = compiled.memory_analysis()
    for rec_key, attr in (("temp_bytes", "temp_size_in_bytes"),
                          ("argument_bytes", "argument_size_in_bytes"),
                          ("output_bytes", "output_size_in_bytes"),
                          ("code_bytes", "generated_code_size_in_bytes")):
      v = getattr(ma, attr, None)
      if v is not None:
        info[rec_key] = int(v)
  except Exception:  # noqa: BLE001 - analysis is best-effort metadata
    pass
  return info


class CompileLog:
  """One-shot AOT compile records + call-through-executable dispatch.

  registry: optional MetricsRegistry — each record's wall time and temp
  bytes are published as `<namespace>/<name>_compile_wall_s` /
  `_temp_bytes` gauges. donate: the donate_argnums the caller built its
  jit fn with (recorded; donation semantics ride the executable itself).
  """

  def __init__(self, registry=None, namespace: str = "compile",
               donate: tuple = ()):
    self._registry = registry
    self._namespace = namespace
    self._donate = tuple(donate)
    # name -> (compiled_or_None, record)
    self._programs: dict = {}

  def Records(self) -> dict:
    """{name: record} — one per compiled program (copies)."""
    return {n: dict(rec) for n, (_, rec) in self._programs.items()}

  def Call(self, name: str, fn, *args):
    """Calls `fn(*args)`, AOT-compiling + recording on first use of
    `name`. `fn` must be a jit wrapper (has .lower); anything else — or
    any compile/dispatch failure — degrades to plain calls forever."""
    entry = self._programs.get(name)
    if entry is None:
      entry = self._Compile(name, fn, args)
    compiled, rec = entry
    if compiled is None:
      return fn(*args)
    try:
      out = compiled(*args)
      rec["calls"] = rec.get("calls", 0) + 1
      return out
    except Exception as e:  # noqa: BLE001 - aval drift: fall back for good
      self._programs[name] = (None, rec)
      rec["fallback"] = f"dispatch: {type(e).__name__}: {e}"
      return fn(*args)

  def Compile(self, name: str, fn, *args) -> None:
    """Builds `name` now, in the caller's thread, from arguments of the
    shapes and dtypes `Call` will be given; a later `Call` finds it. A
    no-op where `name` is already there."""
    if name not in self._programs:
      self._Compile(name, fn, args)

  def _Compile(self, name: str, fn, args):
    rec = {"name": name, "donated_argnums": list(self._donate)}
    compiled = None
    if hasattr(fn, "lower"):
      try:
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        rec["compile_wall_s"] = round(time.perf_counter() - t0, 6)
        rec.update(CompileInfo(compiled))
      except Exception as e:  # noqa: BLE001
        compiled = None
        rec["fallback"] = f"compile: {type(e).__name__}: {e}"
    else:
      rec["fallback"] = "not a jit wrapper (no .lower)"
    if self._registry is not None and "compile_wall_s" in rec:
      self._registry.Gauge(
          f"{self._namespace}/{name}_compile_wall_s").Set(
              rec["compile_wall_s"])
      if "temp_bytes" in rec:
        self._registry.Gauge(
            f"{self._namespace}/{name}_temp_bytes").Set(rec["temp_bytes"])
    self._programs[name] = (compiled, rec)
    return self._programs[name]
