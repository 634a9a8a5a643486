"""What the program's own start-up record says about a run's set-up: the
readers of setup_build_s, setup_step_trace_s, setup_step_lower_s,
setup_step_compile_s, setup_other_programs_s, setup_first_steps_s,
setup_unnamed_s and window_compile_s share this.

The record is lingvo_tpu.observe.profile.Startup(): the set-up phases
(`build`, `compile_step`, `first_steps`), every compile event JAX reported
(trace, lower, compile, fetch; start and end on time.perf_counter, the clock
of run["window"]; the thread; the named program it fell under) and the train
loops with their completions. It is found without an engine, as the step
records are (a reader is handed none).

Set-up is tiled into six parts, by what the record holds:
  the self seconds (`self_s`, as the record's listener took them: an event
    nested in another of its thread counts once) of the compile events under
    a named step program (`ragged`, `feed`; the train `step` and `loop`):
    the traces, the lowerings, the compiles (the backend's, or the cache's
    fetch);
  the self seconds of the compile events under no named program
    (`other_programs`);
  the phase `build` and the phase `first_steps`, each less the time any
    thread was inside a compile event.
Seconds at which two threads compile are in both threads' events:
`overlap_s` in the note `startup_tiling` says how many, and they are taken
out again once, from the last of the four event parts that has them. What
is left of run["setup_s"] is `setup_unnamed_s`: the seven add up to it.
Events and phases count where they end before the window's start: a serve
run's run["window"][0]; a train run's last completion before its window's
loops (the record's loops, the last len(run["intervals"]) of them the
window's).

A serve run's set-up ends (`ctx.SetupEnds`) when its warm-up requests are
done, and its window opens later: after the lead-in, or when the engine is
full. `run` does not carry set-up's end, so what compiles in that ramp counts
among the parts above and is taken out of `setup_unnamed_s`, the remainder.
The note `startup_tiling` therefore says what the ramp held, `ramp_compile_s`
by part: the self seconds of the events that ended after the last step before
the first one the harness recorded behind its warm-up, and before the window.
0.0 expected; anything else is a late compile booked as set-up.

A program without the record (the parent of the PR that brought it) gives
every reader here nothing to read: they return None and never raise for
that."""

from __future__ import annotations

import json

from benchmarks.harness import spans

_EVENT_PARTS = ("step_trace", "step_lower", "step_compile", "other_programs")
PARTS = _EVENT_PARTS + ("build", "first_steps")
_PART_OF_KIND = {"trace": "step_trace", "lower": "step_lower",
                 "compile": "step_compile", "fetch": "step_compile"}
_noted: set = set()
_tilings: dict = {}


def Record():
  """The process's start-up record, or None where the program keeps none."""
  try:
    from lingvo_tpu.observe import profile
    return profile.Startup()
  except (ImportError, AttributeError):
    return None


def Note(key: str, make) -> None:
  """The note `make()` on standard output, once a process however many
  readers ask."""
  if key not in _noted:
    _noted.add(key)
    print(json.dumps({"note": key, "value": make()}, default=str), flush=True)


def WindowStart(run, record) -> float | None:
  """Where set-up's events stop counting (module docstring)."""
  if "window" in run:
    return run["window"][0]
  done = [u.done for u in record.Loops() if u.done is not None]
  n = len(run["intervals"])
  return done[-n - 1] if len(done) > n else None


# -- intervals ----------------------------------------------------------------


def Union(intervals) -> list:
  """Sorted and disjoint, from any (start, end) pairs."""
  out: list = []
  for s, e in sorted(i for i in intervals if i[1] > i[0]):
    if out and s <= out[-1][1]:
      out[-1][1] = max(out[-1][1], e)
    else:
      out.append([s, e])
  return out


def Minus(a, b) -> list:
  """What the sorted disjoint `a` keeps outside the sorted disjoint `b`."""
  out, j = [], 0
  for s, e in a:
    while j < len(b) and b[j][1] <= s:
      j += 1
    k = j
    while k < len(b) and b[k][0] < e:
      if b[k][0] > s:
        out.append([s, b[k][0]])
      s = max(s, b[k][1])
      k += 1
    if s < e:
      out.append([s, e])
  return out


def Length(intervals) -> float:
  return sum(e - s for s, e in intervals)


# -- the tiling ---------------------------------------------------------------


def _PartOf(ev) -> str:
  return _PART_OF_KIND[ev.kind] if ev.program is not None else "other_programs"


def Tile(events, phases, t_end: float) -> dict:
  """{part: seconds} over PARTS plus `overlap_s`, from compile events (each
  with kind, start, end, self_s, thread, program) and phase entries (dicts
  with phase, start, end) that end at or before `t_end`."""
  events = [ev for ev in events if ev.end <= t_end]
  out = {p: 0.0 for p in PARTS}
  by_thread: dict = {}
  for ev in events:
    out[_PartOf(ev)] += ev.self_s
    by_thread.setdefault(ev.thread, []).append((ev.start, ev.end))
  per_thread = [Union(ivs) for ivs in by_thread.values()]
  compiling = Union(iv for ivs in per_thread for iv in ivs)
  # what two threads' events hold at once is in the sums above twice
  out["overlap_s"] = left = max(
      sum(Length(ivs) for ivs in per_thread) - Length(compiling), 0.0)
  for part in reversed(_EVENT_PARTS):
    taken = min(out[part], left)
    out[part], left = out[part] - taken, left - taken
  covered = compiling
  for name in ("build", "first_steps"):
    mine = Union((p["start"], p["end"]) for p in phases
                 if p["phase"] == name and p["end"] <= t_end)
    out[name] = Length(Minus(mine, covered))
    covered = Union(covered + mine)
  return out


def RampStart(run) -> float | None:
  """Where a serve run's warm-up had ended, as far as `run` tells: the end
  of the last step of the program's records before the first step the
  harness recorded behind its warm-up (that step's start where the records
  no longer reach back). None for a train run, whose set-up ends where its
  window starts."""
  if "window" not in run or not run.get("step_records"):
    return None
  t_end, duration = run["step_records"][0][:2]
  first = t_end - duration
  try:
    from lingvo_tpu.observe import trace as trace_lib
    ends = [s.end_ts for rec in trace_lib.Live() for s in rec.Steps()
            if s.end_ts <= first]
  except (ImportError, AttributeError):
    ends = []
  return max(ends, default=first)


def RampCompile(run, events) -> dict:
  """{part: self seconds} of the compile events that ended in a serve run's
  ramp, after RampStart and by the window's start (module docstring)."""
  out = {p: 0.0 for p in _EVENT_PARTS}
  t0 = RampStart(run)
  if t0 is not None:
    for ev in events:
      if t0 < ev.end <= run["window"][0]:
        out[_PartOf(ev)] += ev.self_s
  return out


def Tiling(run) -> dict | None:
  """The run's tiling (once a process), or None with no record to read."""
  record = Record()
  if record is None:
    return None
  t_end = WindowStart(run, record)
  if t_end is None:
    return None
  if t_end not in _tilings:
    _tilings[t_end] = Tile(record.Events(), record.Phases(), t_end)
  return _tilings[t_end]


def Part(run, part: str) -> float | None:
  tiling = Tiling(run)
  return None if tiling is None else tiling[part]


def _Document(run) -> dict:
  """The record up to the window's start, as `/statusz` would carry it."""
  record = Record()
  return record.Document(until=WindowStart(run, record))


def Unnamed(run) -> float | None:
  """run["setup_s"] less the six parts: what the program's record does not
  hold. Prints the tiling as note `startup_tiling` and the record itself up
  to the window's start (phases, programs, the largest of the rest; times
  from the record's zero) as note `startup`, which tools/startup_report.py
  prints as tables."""
  tiling = Tiling(run)
  if tiling is None:
    return None
  setup_s = run["setup_s"]
  unnamed = setup_s - sum(tiling[p] for p in PARTS)
  Note("startup_tiling", lambda: dict(
      {"setup_" + p + "_s": tiling[p] for p in PARTS},
      setup_unnamed_s=unnamed, setup_s=setup_s,
      overlap_s=tiling["overlap_s"],
      ramp_compile_s=RampCompile(run, Record().Events())))
  Note("startup", lambda: _Document(run))
  WindowCompile(run)
  return unnamed


def StepPrograms(run, part: str) -> float | None:
  """One of the named step programs' three parts; prints each program's row
  (what its compile was made of, `cache_hit`, `thread`) as note
  `startup_step_programs`."""
  value = Part(run, part)
  if value is not None:
    Note("startup_step_programs", lambda: _Document(run)["programs"])
  return value


def OtherPrograms(run) -> float | None:
  """Self seconds of the compile events under no named program; prints the
  ten largest `fun_name`s with seconds and count, and the total count, as
  note `startup_other_programs`."""
  value = Part(run, "other_programs")
  if value is not None:
    Note("startup_other_programs", lambda: _Document(run)["other_programs"])
  return value


# -- compiles inside the window -----------------------------------------------


def WindowCompile(run) -> float | None:
  """Seconds that compiled inside the run's window, from the program's own
  records: a serve run's step records that close inside run["window"]
  (StepTrace.compile_s), a train run's window loops (`compile_s` of each
  loop's result). Prints each such step or loop with its programs' names as
  note `window_compile`. None where the records carry no such key."""
  if "window" in run:
    steps = spans.StepRecords(run)
    if not steps or not hasattr(steps[0], "compile_s"):
      return None
    rows = [{"step": s.step, "compile_s": s.compile_s,
             "fun_names": (s.counters or {}).get("compile_fun_names", [])}
            for s in steps if s.compile_s]
  else:
    results = run["loop_results"]
    if not results or "compile_s" not in results[0]:
      return None
    rows = [{"at_step": r.get("at_step"), "compile_s": r["compile_s"],
             "fun_names": r.get("compile_fun_names", [])}
            for r in results if r["compile_s"]]
  total = float(sum(r["compile_s"] for r in rows))
  Note("window_compile", lambda: {"compile_s": total, "rows": rows[:40],
                                  "count": len(rows)})
  return total
