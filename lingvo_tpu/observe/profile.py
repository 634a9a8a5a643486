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
  plan), what the wall time was made of (trace, lowering, backend compile
  or cache fetch: the record IS the program's row of the start-up record
  below), and the donation set, then dispatches every subsequent call
  through the stored executable. The jit tracing cache does not see
  `.lower().compile()`, so the compiled object MUST be reused for
  dispatch or each call would pay tracing again. Any failure — lowering,
  memory_analysis, or an aval mismatch at dispatch — permanently falls
  back to calling the original jit fn for that name, recording why.

- `StartupRecord` / `Startup()`: the process's record of its own set-up,
  always on, on `time.perf_counter`. Three lists, each bounded:
  every compile event JAX reports (`jax.monitoring`: a jaxpr's trace, its
  lowering, the backend's compile or the cache's fetch), with its self time,
  the thread, the named program it fell under and the engine step or train
  loop open on that thread; one row a named program (`Program(name)`, the
  context `CompileLog` and the train programs open round
  `fn.lower(*args).compile()`); and the set-up phases (`Phase(name)`, a
  `lingvo/setup/<name>` TraceAnnotation too). It is the one listener the
  program registers with `jax.monitoring`: `observe.goodput`'s compile
  bucket reads its seconds. Its zero is `lingvo_tpu.T_IMPORT`.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import re
import threading
import time
from typing import Optional

import jax

import lingvo_tpu

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
        # the record is the named program's row: `compile_wall_s` and what
        # it was made of are stamped once, by the start-up record
        with _STARTUP.Program(f"{self._namespace}/{name}", rec):
          compiled = fn.lower(*args).compile()
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


# -- the start-up record ------------------------------------------------------

SETUP_SPAN_PREFIX = "lingvo/setup/"
# what a compile event is, by the jax.monitoring name JAX reports it under
# (each both as a start, `record_scalar`, and as a duration and a time span
# when it ends: jax/_src/dispatch.LogElapsedTimeContextManager)
COMPILE_KIND_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_ANSWERS = {"/jax/compilation_cache/cache_hits": True,
                  "/jax/compilation_cache/cache_misses": False}
# a program's record: the key each kind's self seconds add up under
_RECORD_KEY_OF_KIND = {"trace": "trace_s", "lower": "lower_s",
                       "compile": "backend_s", "fetch": "fetch_s"}
MAX_EVENTS = 32768    # compile events kept; a set-up makes a few thousand
_TOP_OTHER = 10       # `fun_name`s a Document lists under no named program

# One compile event. kind: trace, lower, compile or fetch (a backend event
# that the cache answered with a hit is a `fetch`: the key, the read and the
# load, whole, with no compile in it; JAX's own `cache_retrieval_time_sec` is
# a part of that and comes with no start, so it is not kept beside it);
# start, end: perf_counter; self_s: end - start less the events nested in it
# on its thread; program: the label of the named program open on the thread,
# or None; unit: the engine step or train loop open on it (a Unit), or None.
CompileEvent = collections.namedtuple(
    "CompileEvent", "kind start end self_s fun_name thread program unit")


class Unit:
  """An engine step or a train loop while its record is open on a thread:
  the listener adds to it what compiled there meanwhile."""

  __slots__ = ("kind", "done", "compile_s", "fun_names")

  def __init__(self, kind: str):
    self.kind = kind        # "step" or "loop"
    self.done = None        # a loop's completion (perf_counter)
    self.compile_s = 0.0
    self.fun_names: list = []


class _ThreadState:
  """What the record keeps a thread."""

  __slots__ = ("frames", "program", "unit", "phases", "seconds")

  def __init__(self):
    self.frames: list = []    # open events: [event, start, nested seconds, hit]
    self.program = None       # the named program's row open here
    self.unit = None          # the Unit open here
    self.phases: list = []    # the phases open here, innermost last
    self.seconds = 0.0        # self seconds of every event that ended here


class _Phase:
  """One set-up phase, open. `Close` writes its entry."""

  def __init__(self, record, name, nested):
    self._record = record
    self.name = name
    self.start = None
    self._nested = nested
    self._ann = None
    self.parent = None

  def __enter__(self):
    rec = self._record
    self.start = rec.clock()
    if self._nested:
      stack = rec._Thread().phases
      self.parent = stack[-1].name if stack else None
      stack.append(self)
      self._ann = jax.profiler.TraceAnnotation(SETUP_SPAN_PREFIX + self.name)
      self._ann.__enter__()
    return self

  def __exit__(self, *exc):
    self.Close()
    return False

  def Close(self, end: float | None = None):
    """Ends the phase (once) at `end`, default now, on the caller's thread."""
    rec, self._record = self._record, None
    if rec is None:
      return
    if self._nested:
      self._ann.__exit__(None, None, None)
      stack = rec._Thread().phases
      if self in stack:
        stack.remove(self)
    rec._phases.append({
        "phase": self.name, "start": self.start,
        "end": rec.clock() if end is None else end,
        "thread": threading.get_ident(), "parent": self.parent})


class StartupRecord:
  """The process's record of its own set-up (module docstring).

  zero: the perf_counter stamp every `at_s` counts from. clock: injectable
  (tests feed synthetic events on a fake clock). Every list is bounded: the
  oldest entries fall off, `events_dropped` says how many events did.
  A caller with no thread of its own to speak for passes `thread`."""

  def __init__(self, zero: float | None = None, clock=time.perf_counter):
    self.clock = clock
    self.zero = clock() if zero is None else zero
    self._lock = threading.Lock()
    self._threads: dict[int, _ThreadState] = {}
    self._events = collections.deque(maxlen=MAX_EVENTS)
    self._events_seen = 0
    self._total_s = 0.0
    self._programs = collections.deque(maxlen=1024)
    self._phases = collections.deque(maxlen=1024)
    self._loops = collections.deque(maxlen=4096)

  def _Thread(self, thread: int | None = None) -> _ThreadState:
    ident = threading.get_ident() if thread is None else thread
    ts = self._threads.get(ident)
    if ts is None:
      with self._lock:
        ts = self._threads.setdefault(ident, _ThreadState())
    return ts

  # -- the listener ----------------------------------------------------------

  def EventBegins(self, event: str, thread: int | None = None) -> None:
    """A compile event of COMPILE_KIND_OF_EVENT starts on the thread."""
    self._Thread(thread).frames.append([event, self.clock(), 0.0, None])

  def EventEnds(self, event: str, fun_name: str = "",
                thread: int | None = None) -> None:
    """The innermost open event of that name ends: one entry, with what
    was nested in it taken out of its self time. An end with no start (the
    listener was registered inside it) is left out."""
    ts = self._Thread(thread)
    frames = ts.frames
    for i in range(len(frames) - 1, -1, -1):
      if frames[i][0] == event:
        break
    else:
      return
    _, start, nested, hit = frames[i]
    del frames[i:]
    kind = COMPILE_KIND_OF_EVENT[event]
    if kind == "compile" and hit:
      kind = "fetch"      # the key, the read and the load: no compile ran
    end = self.clock()
    if frames:
      frames[-1][2] += end - start
    self._Add(ts, thread, kind, start, end,
              max(end - start - nested, 0.0), fun_name, hit)

  def CacheAnswered(self, hit: bool, thread: int | None = None) -> None:
    """The compile cache had (or had not) the executable that the backend
    event open on the thread asked for."""
    for frame in reversed(self._Thread(thread).frames):
      if COMPILE_KIND_OF_EVENT[frame[0]] == "compile":
        frame[3] = hit
        break

  @staticmethod
  def _NoteCacheAnswer(row, hit) -> None:
    if row is not None and hit is not None:
      # a program is a hit if the cache had every executable it asked for
      row["cache_hit"] = bool(hit) and row["cache_hit"] is not False

  def _Add(self, ts, thread, kind, start, end, self_s, fun_name, hit) -> None:
    ident = threading.get_ident() if thread is None else thread
    row, unit = ts.program, ts.unit
    ev = CompileEvent(kind, start, end, self_s, fun_name, ident,
                      row["program"] if row is not None else None, unit)
    ts.seconds += self_s
    if row is not None:
      row[_RECORD_KEY_OF_KIND[kind]] += self_s
      self._NoteCacheAnswer(row, hit)
    if unit is not None:
      unit.compile_s += self_s
      if fun_name not in unit.fun_names and len(unit.fun_names) < 32:
        unit.fun_names.append(fun_name)
    with self._lock:
      self._events.append(ev)
      self._events_seen += 1
      self._total_s += self_s

  # -- named programs, phases, steps and loops --------------------------------

  @contextlib.contextmanager
  def Program(self, label: str, row: dict | None = None):
    """Every compile event of this thread inside the block falls under the
    named program `label`. Yields the program's row, whole at the block's
    end: `compile_wall_s` (the block's wall time, the one timer round it),
    the kinds' self seconds (`trace_s`, `lower_s`, `backend_s`: the
    backend's compile, 0 on a hit; `fetch_s`: the cache's key, read and
    load), which add up to at most that, `cache_hit` (None where no cache
    was asked), `thread` and `at_s` (the block's start, from the record's
    zero). row: the caller's compile record, which then IS the row."""
    ts = self._Thread()
    t0 = self.clock()
    row = {} if row is None else row
    row.update(program=label, thread=threading.get_ident(),
               at_s=round(t0 - self.zero, 6), compile_wall_s=0.0,
               trace_s=0.0, lower_s=0.0, backend_s=0.0, fetch_s=0.0,
               cache_hit=None)
    outer, ts.program = ts.program, row
    try:
      yield row
    finally:
      ts.program = outer
      row["compile_wall_s"] = round(self.clock() - t0, 6)
      for k in _RECORD_KEY_OF_KIND.values():
        row[k] = round(row[k], 6)
      self._programs.append(row)

  def Phase(self, name: str) -> _Phase:
    """A set-up phase as a context manager on one thread: a
    `lingvo/setup/<name>` TraceAnnotation and, at its end, one entry
    (phase, start, end, thread, parent: the phase open round it)."""
    return _Phase(self, name, nested=True)

  def OpenPhase(self, name: str) -> _Phase:
    """A phase that starts now on this thread and is ended by `Close()` on
    whichever thread gets there: in the record only, since a
    TraceAnnotation cannot change threads."""
    return _Phase(self, name, nested=False).__enter__()

  def OpenUnit(self, kind: str) -> Unit:
    """An engine step's or a train loop's record opens on this thread."""
    unit = Unit(kind)
    self._Thread().unit = unit
    return unit

  def CloseUnit(self, unit: Unit) -> None:
    ts = self._Thread()
    if ts.unit is unit:
      ts.unit = None

  def LoopDone(self, unit: Unit) -> None:
    """Keeps a train loop's unit (a step's is its StepTrace)."""
    self._loops.append(unit)

  # -- reads -----------------------------------------------------------------

  def Events(self) -> list:
    """The kept CompileEvents, in the order they ended."""
    with self._lock:
      return list(self._events)

  def Programs(self) -> list:
    return [dict(r) for r in list(self._programs)]

  def Phases(self) -> list:
    """The closed phases, in the order they ended (perf_counter times)."""
    return [dict(p) for p in list(self._phases)]

  def Loops(self) -> list:
    return list(self._loops)

  def CompileSeconds(self, thread: int | None = None) -> float:
    """Self seconds of every event that ended on one thread so far."""
    ident = threading.get_ident() if thread is None else thread
    ts = self._threads.get(ident)
    return ts.seconds if ts is not None else 0.0

  def CompileSecondsByThread(self) -> dict:
    with self._lock:
      return {ident: ts.seconds for ident, ts in self._threads.items()}

  def TotalCompileSeconds(self) -> float:
    with self._lock:
      return self._total_s

  def Document(self, until: float | None = None) -> dict:
    """The record as /statusz carries it (`startup`): phases and programs
    with times from the record's zero, the events under no named program
    by `fun_name`, the ten largest as [fun_name, seconds, count,
    {kind: seconds}], and `inside`: the seconds of the events that fell
    inside an engine step or a train loop ({"step": s, "loop": s}; the
    first steps' and a late compile's alike).
    until: only what ended by that perf_counter stamp (a benchmark run's
    set-up ends where its window starts)."""
    ended = (lambda t: True) if until is None else (lambda t: t <= until)
    events = [ev for ev in self.Events() if ended(ev.end)]
    other: dict = {}
    inside = {"step": 0.0, "loop": 0.0}
    for ev in events:
      if ev.unit is not None:
        inside[ev.unit.kind] += ev.self_s
      if ev.program is None:
        entry = other.setdefault(ev.fun_name, [0.0, 0, {}])
        entry[0] += ev.self_s
        entry[1] += 1
        entry[2][ev.kind] = entry[2].get(ev.kind, 0.0) + ev.self_s
    ranked = sorted(other.items(), key=lambda kv: -kv[1][0])
    with self._lock:
      seen = self._events_seen
    return {
        "phases": [{"phase": p["phase"],
                    "start_s": round(p["start"] - self.zero, 6),
                    "end_s": round(p["end"] - self.zero, 6),
                    "thread": p["thread"], "parent": p["parent"]}
                   for p in self.Phases() if ended(p["end"])],
        "programs": [r for r in self.Programs()
                     if ended(self.zero + r["at_s"] + r["compile_wall_s"])],
        "other_programs": {
            "seconds": round(sum(v[0] for v in other.values()), 6),
            "events": sum(v[1] for v in other.values()),
            "top": [[name, round(v[0], 6), v[1],
                     {k: round(x, 6) for k, x in v[2].items()}]
                    for name, v in ranked[:_TOP_OTHER]]},
        "inside": {k: round(v, 6) for k, v in inside.items()},
        "events_dropped": seen - len(self._events),
    }


_STARTUP = StartupRecord(zero=lingvo_tpu.T_IMPORT)


def Startup() -> StartupRecord:
  """The process's start-up record (reachable without an engine)."""
  return _STARTUP


def InPhase(name: str):
  """Decorator: the function's every call is the set-up phase `name`."""
  def _Wrap(fn):
    @functools.wraps(fn)
    def _InPhase(*args, **kwargs):
      with _STARTUP.Phase(name):
        return fn(*args, **kwargs)
    return _InPhase
  return _Wrap


def _OnScalar(event: str, value, **_) -> None:
  del value
  if event in COMPILE_KIND_OF_EVENT:
    _STARTUP.EventBegins(event)


def _OnDuration(event: str, duration_s: float, **kw) -> None:
  del duration_s      # the record stamps both ends itself
  if event in COMPILE_KIND_OF_EVENT:
    _STARTUP.EventEnds(event, str(kw.get("fun_name", "")))


def _OnEvent(event: str, **_) -> None:
  hit = _CACHE_ANSWERS.get(event)
  if hit is not None:
    _STARTUP.CacheAnswered(hit)


def _Register() -> None:
  """Once a process, at import: the record is always on. An event's start
  comes as a scalar and its end as a duration (the time-span form of the
  same end carries time.time() stamps: the record keeps perf_counter, and
  stamps both ends itself, on the thread that compiles)."""
  try:
    jax.monitoring.register_scalar_listener(_OnScalar)
    jax.monitoring.register_event_duration_secs_listener(_OnDuration)
    jax.monitoring.register_event_listener(_OnEvent)
  except Exception:  # noqa: BLE001 - accounting must never break jax
    pass


_Register()
