"""Per-request serving traces: ring-buffer recorder + Chrome trace export.

ISSUE 12 pillar 1. The serving engine emits lifecycle events — submit →
admit (slot + pages) → prefill chunk(s) → per-token decode / spec-verify
with accepted length → rollback → retire (eos/length/cancelled) — into a
`TraceRecorder`. Two storage tiers make it lock-cheap AND lossless where
it matters:

- a bounded **ring buffer** of raw events (`deque(maxlen=capacity)`):
  constant memory under any load; old events fall off the back.
- a per-request **record** (`RequestTrace`) updated on every event:
  open requests are NEVER evicted, so a request's lifecycle survives any
  amount of ring wraparound (the wraparound-without-loss satellite);
  completed records move to a second bounded deque.

Derived per-request metrics (queue_wait, TTFT, per-output-token latency,
tokens, pages held, spec acceptance) come from the records.
`ChromeTrace()` exports the Chrome trace-event JSON format — open the file
in Perfetto (ui.perfetto.dev) and each decode slot is one row, with every
request's queued/prefill/decode phases as nested duration events and
spec-verify/rollback instants on top. `tools/trace_report.py` turns the
same file into a latency table.

Every Emit is a timestamp + deque append + a few record-field updates
under one lock — no allocation-heavy formatting on the hot path; all
derivation happens at export time.

Beside the per-request record the recorder keeps a per-STEP record
(`StepTrace`, one `StepDone()` call per engine step): where one iteration
of the engine loop spent its host time, phase by phase (`STEP_SEGMENTS`),
and how long the loop took to come back for it (`loop_s`). The engine
measures the phases on this recorder's clock and opens a
`jax.profiler.TraceAnnotation` of the same name (`lingvo/serve/<phase>`)
around each, so a profiler trace shows the same spans on the host plane
beside the device ops. Steps live in a deque of their own: token events
cannot evict them. `ChromeTrace()` draws them as one more row, "engine
loop", and carries them under `perStep`.

Recorders register in a weak set: `Live()` is how a process's exporter
(or a benchmark reader, which is handed no engine) finds them.
"""

from __future__ import annotations

import json
import threading
import time
import weakref
from typing import Optional

# Chrome-trace row used for requests that were never admitted to a slot
# (cancelled while queued). Real slots are tids 0..max_batch-1.
_QUEUE_ONLY_TID = 10**6
# Chrome-trace row of the per-step records.
_ENGINE_LOOP_TID = 10**6 + 1

# The segments of one engine step, in the order they happen; each is a
# `lingvo/serve/<name>` span in a profiler trace too. `lock_wait` comes
# twice (the engine lock is taken before admit and again before commit):
# StepTrace.Phases() adds the two. `draft` is zero without a draft source.
STEP_SEGMENTS = ("lock_wait", "admit", "build", "draft", "h2d", "dispatch",
                 "device_wait", "lock_wait", "commit")
STEP_PHASES = tuple(dict.fromkeys(STEP_SEGMENTS))

_LIVE: "weakref.WeakSet[TraceRecorder]" = weakref.WeakSet()
_LIVE_LOCK = threading.Lock()
# The recorder that took the newest step record is kept reachable here, so
# the steps that led up to an engine's end can still be read once the
# engine itself is garbage (a post-mortem exporter, the benchmark's
# readers). One reference, replaced by the next engine that steps.
_last_stepped: Optional["TraceRecorder"] = None


def Live() -> list:
  """Every TraceRecorder of this process that is still alive, oldest
  first. A recorder nobody references is forgotten, except the one that
  recorded the newest engine step (see _last_stepped)."""
  with _LIVE_LOCK:
    return sorted(_LIVE, key=lambda r: r.epoch)


class StepTrace:
  """One engine step: when it started, how long the loop took to come back
  for it, and the seconds spent in each of STEP_SEGMENTS (recorder-clock
  seconds throughout)."""

  __slots__ = ("step", "start_ts", "loop_s", "segments_s", "valid_tokens",
               "prefill_tokens", "rows", "counters", "compile_s")

  def __init__(self, step, start_ts, loop_s, segments_s, valid_tokens,
               prefill_tokens, rows, counters=None, compile_s=0.0):
    self.step = step
    self.start_ts = start_ts
    self.loop_s = loop_s
    self.segments_s = segments_s
    self.valid_tokens = valid_tokens
    self.prefill_tokens = prefill_tokens
    self.rows = rows
    # {name: value so far} of the engine's cumulative counters that a
    # reader wants between two steps (expert load, window pages), as they
    # stood when the step's record closed; None where the engine has none.
    # A step that compiled carries the programs' names beside them, under
    # `compile_fun_names`
    self.counters = counters
    # seconds of compile events (observe.profile's start-up record: trace,
    # lowering, backend compile or cache fetch, self time) that ended on the
    # engine's thread while this record was open; 0.0 for a step that found
    # its programs built
    self.compile_s = compile_s

  @property
  def span_s(self) -> float:
    """The `lingvo/serve/step` span: the segments tile it."""
    return sum(self.segments_s)

  @property
  def end_ts(self) -> float:
    return self.start_ts + self.span_s

  def Phases(self) -> dict:
    """{phase: seconds}, the two lock waits added."""
    out = dict.fromkeys(STEP_PHASES, 0.0)
    for name, s in zip(STEP_SEGMENTS, self.segments_s):
      out[name] += s
    return out

  def Metrics(self) -> dict:
    return {"step": self.step, "start_s": self.start_ts,
            "span_s": self.span_s, "loop_s": self.loop_s,
            "phases_s": self.Phases(), "valid_tokens": self.valid_tokens,
            "prefill_tokens": self.prefill_tokens, "rows": self.rows,
            "compile_s": self.compile_s,
            "compile_fun_names": (self.counters or {}).get(
                "compile_fun_names", [])}


class RequestTrace:
  """One request's lifecycle record (timestamps are recorder-clock
  seconds; see TraceRecorder for which event sets which field)."""

  __slots__ = (
      "req_id", "slot", "prompt_tokens", "max_new", "pages",
      "submit_ts", "admit_ts", "first_token_ts", "last_token_ts",
      "retire_ts", "finish_reason", "tokens", "prefill_chunks",
      "prefill_tokens", "spec_cycles", "draft_tokens", "accepted_tokens",
      "rolled_back_tokens", "prefix_hit_tokens",
  )

  def __init__(self, req_id):
    self.req_id = req_id
    self.slot: Optional[int] = None
    self.prompt_tokens = 0
    self.max_new = 0
    self.pages = 0
    self.submit_ts: Optional[float] = None
    self.admit_ts: Optional[float] = None
    self.first_token_ts: Optional[float] = None
    self.last_token_ts: Optional[float] = None
    self.retire_ts: Optional[float] = None
    self.finish_reason: Optional[str] = None
    self.tokens = 0
    self.prefill_chunks = 0
    self.prefill_tokens = 0
    self.spec_cycles = 0
    self.draft_tokens = 0
    self.accepted_tokens = 0
    self.rolled_back_tokens = 0
    self.prefix_hit_tokens = 0

  @property
  def complete(self) -> bool:
    return self.submit_ts is not None and self.retire_ts is not None

  def Metrics(self) -> dict:
    """Derived per-request metrics (None where the phase never happened)."""
    queue_wait = (self.admit_ts - self.submit_ts
                  if self.admit_ts is not None else None)
    ttft = (self.first_token_ts - self.submit_ts
            if self.first_token_ts is not None else None)
    # per-output-token latency over the decode phase (first token lands
    # with the final prefill chunk, so it is excluded from the rate)
    tpot = None
    if self.first_token_ts is not None and self.tokens > 1:
      tpot = ((self.last_token_ts - self.first_token_ts)
              / (self.tokens - 1))
    total = (self.retire_ts - self.submit_ts
             if self.complete else None)
    out = {
        "req_id": self.req_id,
        "slot": self.slot,
        "prompt_tokens": self.prompt_tokens,
        "max_new": self.max_new,
        "tokens": self.tokens,
        "pages": self.pages,
        "finish_reason": self.finish_reason,
        "queue_wait_s": queue_wait,
        "ttft_s": ttft,
        "tpot_s": tpot,
        "total_s": total,
        "prefill_chunks": self.prefill_chunks,
    }
    if self.draft_tokens:
      out["spec_cycles"] = self.spec_cycles
      out["draft_tokens"] = self.draft_tokens
      out["accepted_tokens"] = self.accepted_tokens
      out["spec_acceptance"] = self.accepted_tokens / self.draft_tokens
      out["rolled_back_tokens"] = self.rolled_back_tokens
    if self.prefix_hit_tokens:
      out["prefix_hit_tokens"] = self.prefix_hit_tokens
    return out


class TraceRecorder:
  """Lock-cheap lifecycle recorder (module docstring).

  capacity: raw-event ring size. completed_capacity: retained completed
  request records (oldest evicted first). step_capacity: retained step
  records (oldest evicted first; the default holds five minutes of 10 ms
  steps). clock: timestamp source — injectable for deterministic tests.
  """

  # event kind -> record update, dispatched in Emit
  KINDS = ("submit", "prefix_hit", "admit", "prefill_chunk", "token",
           "spec_verify", "rollback", "retire")

  def __init__(self, capacity: int = 8192, completed_capacity: int = 4096,
               clock=time.perf_counter, step_capacity: int = 32768):
    import collections
    assert capacity >= 1 and completed_capacity >= 1 and step_capacity >= 1
    self._clock = clock
    self._lock = threading.Lock()
    self._ring = collections.deque(maxlen=capacity)
    self._open: dict = {}
    self._completed = collections.deque(maxlen=completed_capacity)
    self._steps = collections.deque(maxlen=step_capacity)
    self._emitted = 0
    self._steps_recorded = 0
    self.epoch = clock()
    with _LIVE_LOCK:
      _LIVE.add(self)

  @property
  def clock(self):
    """The timestamp source; the engine times its step phases on it."""
    return self._clock

  # -- emission (hot path; one lock, no formatting) --------------------------

  def Emit(self, kind: str, req_id, a: int = 0, b: int = 0,
           reason: Optional[str] = None):
    """Records one event. (a, b) are kind-specific small ints:
    submit(prompt_tokens, max_new) · prefix_hit(tokens) ·
    admit(slot, pages) · prefill_chunk(tokens) · token(n) ·
    spec_verify(drafted, accepted) · rollback(tokens) ·
    retire(pages_freed) + reason."""
    ts = self._clock()
    with self._lock:
      self._ring.append((ts, kind, req_id, a, b, reason))
      self._emitted += 1
      rec = self._open.get(req_id)
      if rec is None:
        if kind != "submit":
          return  # unknown/already-retired request: keep the raw event only
        rec = RequestTrace(req_id)
        self._open[req_id] = rec
        rec.submit_ts = ts
        rec.prompt_tokens = a
        rec.max_new = b
      elif kind == "prefix_hit":
        rec.prefix_hit_tokens += a
      elif kind == "admit":
        rec.admit_ts = ts
        rec.slot = a
        rec.pages = b
      elif kind == "prefill_chunk":
        rec.prefill_chunks += 1
        rec.prefill_tokens += a
      elif kind == "token":
        if rec.first_token_ts is None:
          rec.first_token_ts = ts
        rec.last_token_ts = ts
        rec.tokens += a
      elif kind == "spec_verify":
        rec.spec_cycles += 1
        rec.draft_tokens += a
        rec.accepted_tokens += b
      elif kind == "rollback":
        rec.rolled_back_tokens += a
      elif kind == "retire":
        rec.retire_ts = ts
        rec.finish_reason = reason
        del self._open[req_id]
        self._completed.append(rec)

  # convenience emitters (one per lifecycle kind)
  def Submit(self, req_id, prompt_tokens: int = 0, max_new: int = 0):
    self.Emit("submit", req_id, prompt_tokens, max_new)

  def PrefixHit(self, req_id, tokens: int):
    """Prompt tokens served from the prefix cache (between submit and
    admit: the hit is resolved during the admission the request wins)."""
    self.Emit("prefix_hit", req_id, tokens)

  def Admit(self, req_id, slot: int, pages: int = 0):
    self.Emit("admit", req_id, slot, pages)

  def PrefillChunk(self, req_id, tokens: int):
    self.Emit("prefill_chunk", req_id, tokens)

  def Token(self, req_id, n: int = 1):
    self.Emit("token", req_id, n)

  def SpecVerify(self, req_id, drafted: int, accepted: int):
    self.Emit("spec_verify", req_id, drafted, accepted)

  def Rollback(self, req_id, tokens: int):
    self.Emit("rollback", req_id, tokens)

  def Retire(self, req_id, reason: str, pages_freed: int = 0):
    self.Emit("retire", req_id, pages_freed, reason=reason)

  def StepDone(self, step: int, start_ts: float, loop_s: float, segments_s,
               valid_tokens: int = 0, prefill_tokens: int = 0,
               rows: int = 0, counters=None, compile_s: float = 0.0):
    """Records one engine step: the one call a step costs. segments_s: the
    seconds spent in each of STEP_SEGMENTS, which tile the step from
    start_ts on; loop_s: from the previous step's end to start_ts;
    compile_s: StepTrace.compile_s."""
    global _last_stepped
    rec = StepTrace(step, start_ts, loop_s, tuple(segments_s), valid_tokens,
                    prefill_tokens, rows, counters, compile_s)
    assert len(rec.segments_s) == len(STEP_SEGMENTS), rec.segments_s
    with self._lock:
      self._steps.append(rec)
      self._steps_recorded += 1
    _last_stepped = self

  # -- reads -----------------------------------------------------------------

  def Events(self) -> list:
    """Raw ring contents, oldest first: (ts, kind, req_id, a, b, reason)."""
    with self._lock:
      return list(self._ring)

  def Requests(self) -> dict:
    """{req_id: RequestTrace} — open AND retained completed records."""
    with self._lock:
      out = {r.req_id: r for r in self._completed}
      out.update(self._open)
      return out

  def Get(self, req_id) -> Optional[RequestTrace]:
    return self.Requests().get(req_id)

  def Steps(self) -> list:
    """Retained StepTrace records, oldest first."""
    with self._lock:
      return list(self._steps)

  def PerRequestMetrics(self) -> dict:
    return {rid: rec.Metrics() for rid, rec in self.Requests().items()}

  def Stats(self) -> dict:
    with self._lock:
      return {
          "events_buffered": len(self._ring),
          "events_dropped": self._emitted - len(self._ring),
          "requests_open": len(self._open),
          "requests_completed": len(self._completed),
          "steps_recorded": self._steps_recorded,
          "steps_buffered": len(self._steps),
      }

  # -- Chrome trace-event export ---------------------------------------------

  def _Us(self, ts: float) -> float:
    return (ts - self.epoch) * 1e6

  def ChromeTrace(self) -> dict:
    """Chrome trace-event JSON (object form): one pid ("serving"), one tid
    per decode slot, per-request queued/prefill/decode duration pairs plus
    spec-verify/rollback instants from the ring, and one more row, "engine
    loop", with every retained step and its segments as nested duration
    pairs. Extra top-level keys `perRequest` and `perStep` carry the
    derived metrics (ignored by viewers, consumed by
    tools/trace_report.py)."""
    records = self.Requests()
    raw = self.Events()
    steps = self.Steps()
    ev = [{"ph": "M", "pid": 0, "tid": 0, "name": "process_name",
           "args": {"name": "serving"}}]
    tids = {}
    for rec in records.values():
      tid = rec.slot if rec.slot is not None else _QUEUE_ONLY_TID
      tids.setdefault(
          tid, f"slot {rec.slot}" if rec.slot is not None else "queued-only")

    def _Span(name, tid, t0, t1, args=None):
      if t0 is None or t1 is None:
        return  # phase never completed: no unmatched B without its E
      ev.append({"ph": "B", "pid": 0, "tid": tid, "name": name,
                 "cat": "serving", "ts": self._Us(t0),
                 **({"args": args} if args else {})})
      ev.append({"ph": "E", "pid": 0, "tid": tid, "name": name,
                 "cat": "serving", "ts": self._Us(t1)})

    per_request = {}
    for rec in records.values():
      tid = rec.slot if rec.slot is not None else _QUEUE_ONLY_TID
      name = f"req {rec.req_id}"
      m = rec.Metrics()
      per_request[str(rec.req_id)] = m
      # queued: submit -> admit (or retire, for cancelled-while-queued)
      _Span(f"{name} queued", tid, rec.submit_ts,
            rec.admit_ts if rec.admit_ts is not None else rec.retire_ts,
            {"prompt_tokens": rec.prompt_tokens, "max_new": rec.max_new})
      # prefill: admit -> first token (the first token IS the final
      # prefill chunk's sample, so this span covers all prompt chunks)
      _Span(f"{name} prefill", tid, rec.admit_ts, rec.first_token_ts,
            {"prompt_tokens": rec.prompt_tokens, "pages": rec.pages,
             "chunks": rec.prefill_chunks})
      # decode: first token -> retire, args carry the derived metrics
      _Span(f"{name} decode", tid, rec.first_token_ts, rec.retire_ts,
            {k: v for k, v in m.items() if v is not None})
    for ts, kind, req_id, a, b, _reason in raw:
      if kind not in ("spec_verify", "rollback"):
        continue
      rec = records.get(req_id)
      tid = (rec.slot if rec is not None and rec.slot is not None
             else _QUEUE_ONLY_TID)
      args = ({"drafted": a, "accepted": b} if kind == "spec_verify"
              else {"tokens": a})
      ev.append({"ph": "i", "pid": 0, "tid": tid, "s": "t",
                 "name": f"{kind} req {req_id}", "cat": "serving",
                 "ts": self._Us(ts), "args": args})
    per_step = []
    if steps:
      tids[_ENGINE_LOOP_TID] = "engine loop"
    for st in steps:
      per_step.append(st.Metrics())
      # the step's B first and its E last, each segment's end computed
      # the way the next one's start is: pairs nest after the sort below
      row = {"pid": 0, "tid": _ENGINE_LOOP_TID, "cat": "serving"}
      ev.append({"ph": "B", "name": f"step {st.step}",
                 "ts": self._Us(st.start_ts), **row,
                 "args": {"valid_tokens": st.valid_tokens,
                          "prefill_tokens": st.prefill_tokens,
                          "rows": st.rows, "loop_ms": st.loop_s * 1e3,
                          "compile_ms": st.compile_s * 1e3}})
      t = st.start_ts
      for name, dur in zip(STEP_SEGMENTS, st.segments_s):
        if dur > 0:
          _Span(name, _ENGINE_LOOP_TID, t, t + dur)
        t += dur
      ev.append({"ph": "E", "name": f"step {st.step}", "ts": self._Us(t),
                 **row})
    for tid, label in sorted(tids.items()):
      ev.append({"ph": "M", "pid": 0, "tid": tid, "name": "thread_name",
                 "args": {"name": label}})
    # stable order: metadata first, then by timestamp with E before B at
    # shared endpoints (adjacent phases touch), instants after the B
    phase_rank = {"M": -1, "E": 0, "B": 1, "i": 2}
    ev.sort(key=lambda e: (e.get("ts", -1), phase_rank.get(e["ph"], 3)))
    return {"traceEvents": ev, "displayTimeUnit": "ms",
            "perRequest": per_request, "perStep": per_step}

  def Export(self, path: str) -> dict:
    """Writes ChromeTrace() JSON to `path`; returns the trace dict."""
    trace = self.ChromeTrace()
    with open(path, "w") as f:
      json.dump(trace, f)
    return trace
