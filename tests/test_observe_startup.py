"""Set-up under the program's own names (docs/observability.md, "Start-up
record"): observe.profile's StartupRecord, the process's one jax.monitoring
listener.

- the listener: an event nested in another of its thread counts its self
  time only, two threads are kept apart, a backend event the cache answered
  with a hit is a `fetch`, an end with no start is left out, the list is
  bounded;
- a compile record says what it was made of, and the kinds never add up to
  more than its `compile_wall_s`;
- the phases of a tiny ServingLoop and of a tiny TrainProgram nest and lie
  in order on one clock, each a `lingvo/setup/<phase>` span too;
- an event that ends while a step's record is open lands in that step's
  `compile_s` and in no other's; a train loop's result carries the same;
- `/statusz` carries the record, `tools/startup_report.py` prints it, and
  `tools/trace_report.py` names the step that compiled.
"""

import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu import observe
from lingvo_tpu.observe import profile as profile_lib
from lingvo_tpu.observe import schema as observe_schema
from lingvo_tpu.observe import trace as trace_lib

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import startup_report  # noqa: E402
import trace_report  # noqa: E402

from tests.test_observe_export import _FakeClock
from tests.test_observe_spans import _Engine, _HostSpans, _TrainProgram
from tests.test_observe_spans import tiny_lm  # noqa: F401  (a fixture)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
KIND_KEYS = ("trace_s", "lower_s", "backend_s", "fetch_s")
# what a compile record says it was made of, beside `compile_wall_s`
RECORD_KEYS = KIND_KEYS + ("cache_hit", "thread", "at_s", "program")


def _Record(t0=100.0):
  clock = _FakeClock(t0)
  return profile_lib.StartupRecord(clock=clock), clock


def _Event(rec, clock, event, seconds, fun_name="f", thread=None, inside=()):
  """One synthetic event `seconds` long with `inside` run in its middle."""
  rec.EventBegins(event, thread=thread)
  clock.t += seconds / 2
  for fn in inside:
    fn()
  clock.t += seconds / 2
  rec.EventEnds(event, fun_name, thread=thread)


# -- the listener on a fake clock ---------------------------------------------


class TestListener:

  def test_a_nested_event_counts_its_self_time_once(self):
    rec, clock = _Record()
    inner = lambda: _Event(rec, clock, TRACE, 1.0, "inner")  # noqa: E731
    _Event(rec, clock, TRACE, 4.0, "outer", inside=[inner, inner])
    evs = {e.fun_name: e for e in rec.Events()}
    assert [e.fun_name for e in rec.Events()] == ["inner", "inner", "outer"]
    assert evs["outer"].self_s == pytest.approx(4.0)
    assert evs["outer"].end - evs["outer"].start == pytest.approx(6.0)
    assert rec.TotalCompileSeconds() == pytest.approx(6.0)   # the wall, once
    assert rec.CompileSeconds() == pytest.approx(6.0)

  def test_three_deep_and_siblings(self):
    rec, clock = _Record()
    leaf = lambda: _Event(rec, clock, TRACE, 0.5, "leaf")  # noqa: E731
    mid = lambda: _Event(rec, clock, TRACE, 1.0, "mid",  # noqa: E731
                         inside=[leaf])
    _Event(rec, clock, TRACE, 2.0, "top", inside=[mid])
    _Event(rec, clock, LOWER, 3.0, "top")
    by = {(e.kind, e.fun_name): e.self_s for e in rec.Events()}
    assert by == pytest.approx({("trace", "leaf"): 0.5, ("trace", "mid"): 1.0,
                                ("trace", "top"): 2.0, ("lower", "top"): 3.0})

  def test_two_threads_are_kept_apart(self):
    rec, clock = _Record()
    rec.EventBegins(TRACE, thread=1)
    clock.t += 1.0
    rec.EventBegins(BACKEND, thread=2)      # beside it, not inside it
    clock.t += 2.0
    rec.EventEnds(BACKEND, "other", thread=2)
    clock.t += 1.0
    rec.EventEnds(TRACE, "mine", thread=1)
    by = {e.fun_name: e for e in rec.Events()}
    assert by["mine"].self_s == pytest.approx(4.0) and by["mine"].thread == 1
    assert by["other"].self_s == pytest.approx(2.0) and by["other"].thread == 2
    assert rec.CompileSeconds(1) == pytest.approx(4.0)
    assert rec.CompileSeconds(2) == pytest.approx(2.0)
    assert rec.CompileSecondsByThread() == pytest.approx({1: 4.0, 2: 2.0})
    assert rec.CompileSeconds(3) == 0.0

  def test_real_threads_have_their_own_stacks(self):
    rec = profile_lib.StartupRecord()
    gate = threading.Barrier(2)

    def _Work(name):
      rec.EventBegins(TRACE)
      gate.wait(timeout=10)                 # both open at once
      rec.EventEnds(TRACE, name)

    threads = [threading.Thread(target=_Work, args=(n,)) for n in "ab"]
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    evs = rec.Events()
    assert sorted(e.fun_name for e in evs) == ["a", "b"]
    assert len({e.thread for e in evs}) == 2
    assert all(e.self_s == pytest.approx(e.end - e.start) for e in evs)

  @pytest.mark.parametrize("hit,kind,key", [(True, "fetch", "fetch_s"),
                                            (False, "compile", "backend_s"),
                                            (None, "compile", "backend_s")])
  def test_a_backend_event_the_cache_answered(self, hit, kind, key):
    rec, clock = _Record()
    answered = [] if hit is None else [lambda: rec.CacheAnswered(hit)]
    with rec.Program("p") as row:
      _Event(rec, clock, BACKEND, 2.0, "jit(f)", inside=answered)
    assert [e.kind for e in rec.Events()] == [kind]
    assert row[key] == pytest.approx(2.0) and row["cache_hit"] is hit
    assert sum(row[k] for k in KIND_KEYS) == pytest.approx(2.0)

  def test_a_program_is_a_hit_only_if_every_executable_was(self):
    rec, clock = _Record()
    with rec.Program("p") as row:
      _Event(rec, clock, BACKEND, 1.0, inside=[lambda: rec.CacheAnswered(True)])
      _Event(rec, clock, BACKEND, 1.0,
             inside=[lambda: rec.CacheAnswered(False)])
      _Event(rec, clock, BACKEND, 1.0, inside=[lambda: rec.CacheAnswered(True)])
    assert row["cache_hit"] is False
    assert row["fetch_s"] == pytest.approx(2.0)
    assert row["backend_s"] == pytest.approx(1.0)

  def test_an_end_with_no_start_is_left_out(self):
    rec, clock = _Record()
    rec.EventEnds(TRACE, "registered inside it")
    assert rec.Events() == [] and rec.TotalCompileSeconds() == 0.0
    # and an open event of another name is not taken for it
    rec.EventBegins(LOWER)
    rec.EventEnds(TRACE, "still none")
    clock.t += 1.0
    rec.EventEnds(LOWER, "f")
    assert [(e.kind, e.self_s) for e in rec.Events()] == [("lower", 1.0)]

  def test_the_list_is_bounded_and_says_what_it_dropped(self, monkeypatch):
    clock = _FakeClock()
    monkeypatch.setattr(profile_lib, "MAX_EVENTS", 4)
    rec = profile_lib.StartupRecord(clock=clock)
    for i in range(10):
      _Event(rec, clock, TRACE, 1.0, f"f{i}")
    assert [e.fun_name for e in rec.Events()] == ["f6", "f7", "f8", "f9"]
    doc = rec.Document()
    assert doc["events_dropped"] == 6
    assert doc["other_programs"]["events"] == 4
    assert rec.TotalCompileSeconds() == pytest.approx(10.0)   # the sum stays

  def test_events_under_no_program_are_kept_by_fun_name(self, monkeypatch):
    rec, clock = _Record()
    for name, s in (("add", 0.25), ("jit(gather)", 2.0), ("add", 0.25)):
      _Event(rec, clock, TRACE, s, name)
    with rec.Program("p"):
      _Event(rec, clock, TRACE, 5.0, "named")
    other = rec.Document()["other_programs"]
    assert other["events"] == 3 and other["seconds"] == pytest.approx(2.5)
    assert other["top"] == [["jit(gather)", 2.0, 1, {"trace": 2.0}],
                            ["add", 0.5, 2, {"trace": 0.5}]]
    monkeypatch.setattr(profile_lib, "_TOP_OTHER", 1)
    other = rec.Document()["other_programs"]
    assert other["events"] == 3 and len(other["top"]) == 1

  def test_the_process_has_one_duration_listener_of_the_programs(self):
    from jax._src import monitoring
    from lingvo_tpu.observe import goodput as goodput_lib
    goodput_lib.Get()
    mine = [cb for cb in monitoring.get_event_duration_listeners()
            if getattr(cb, "__module__", "").startswith("lingvo_tpu")]
    assert mine == [profile_lib._OnDuration]
    assert profile_lib.Startup() is observe.Startup()
    import lingvo_tpu
    assert profile_lib.Startup().zero == lingvo_tpu.T_IMPORT


# -- named programs and compile records ---------------------------------------


class TestCompileRecord:

  def test_kinds_of_a_synthetic_program_add_up_to_its_wall(self):
    rec, clock = _Record()
    inner = lambda: _Event(rec, clock, TRACE, 1.0, "where")  # noqa: E731
    with rec.Program("serving/compile/ragged") as row:
      clock.t += 0.25                                   # glue, no event
      _Event(rec, clock, TRACE, 3.0, "step", inside=[inner])
      _Event(rec, clock, LOWER, 2.0, "jit(step)")
      _Event(rec, clock, BACKEND, 5.0, "jit(step)",
             inside=[lambda: rec.CacheAnswered(True)])
    _Event(rec, clock, TRACE, 7.0, "after")             # not the program's
    assert row == rec.Programs()[0]
    assert (row["trace_s"], row["lower_s"], row["backend_s"],
            row["fetch_s"]) == pytest.approx((4.0, 2.0, 0.0, 5.0))
    assert row["compile_wall_s"] == pytest.approx(11.25)
    assert sum(row[k] for k in KIND_KEYS) <= row["compile_wall_s"]
    assert row["cache_hit"] is True
    assert row["at_s"] == pytest.approx(0.0)
    assert [e.program for e in rec.Events()] == (
        ["serving/compile/ragged"] * 4 + [None])

  def test_a_compile_log_record_says_what_it_was_made_of(self):
    log = observe.CompileLog(namespace="t/compile")
    fn = jax.jit(lambda x: jnp.tanh(x) @ x.T + jnp.where(x > 0, x, 0.0).sum())
    x = jnp.ones((16, 16))
    out = log.Call("f", fn, x)
    np.testing.assert_allclose(out, fn(x))
    rec = log.Records()["f"]
    for k in RECORD_KEYS:
      assert k in rec, k
    kinds = sum(rec[k] for k in KIND_KEYS)
    assert 0 < kinds <= rec["compile_wall_s"] + 1e-6
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0
    assert rec["backend_s"] + rec["fetch_s"] > 0
    assert rec["thread"] == threading.get_ident() and rec["at_s"] > 0
    row = [r for r in profile_lib.Startup().Programs()
           if r["program"] == "t/compile/f"][-1]
    assert row == rec              # one row, the record's and the log's
    mine = [e for e in profile_lib.Startup().Events()
            if e.program == "t/compile/f"]
    assert {e.kind for e in mine} >= {"trace", "lower"}
    assert sum(e.self_s for e in mine) == pytest.approx(kinds, abs=1e-4)


# -- phases -------------------------------------------------------------------


def _PhasesSince(t):
  """The phases that began after `t` on the record's clock (by time, not by
  position: the record's lists are bounded and drop their oldest)."""
  return [p for p in profile_lib.Startup().Phases() if p["start"] >= t]


def _Inside(inner, outer):
  return outer["start"] <= inner["start"] <= inner["end"] <= outer["end"]


class TestPhases:

  def test_phases_nest_on_a_fake_clock(self):
    rec, clock = _Record(10.0)
    with rec.Phase("build"):
      clock.t += 1.0
      with rec.Phase("states"):
        clock.t += 2.0
      clock.t += 1.0
    opened = rec.OpenPhase("first_steps")
    clock.t += 3.0
    opened.Close()
    opened.Close()                                      # once
    assert [(p["phase"], p["start"], p["end"], p["parent"])
            for p in rec.Phases()] == [
                ("states", 11.0, 13.0, "build"), ("build", 10.0, 14.0, None),
                ("first_steps", 14.0, 17.0, None)]
    doc = rec.Document()["phases"]
    assert [(p["phase"], p["start_s"], p["end_s"]) for p in doc] == [
        ("states", 1.0, 3.0), ("build", 0.0, 4.0), ("first_steps", 4.0, 7.0)]
    # up to a stamp: what had ended by then
    assert [p["phase"] for p in rec.Document(until=14.0)["phases"]] == [
        "states", "build"]

  def test_a_phase_closed_on_another_thread(self):
    rec, clock = _Record(50.0)
    opened = rec.OpenPhase("first_steps")
    clock.t += 20.0
    t = threading.Thread(target=lambda: opened.Close(end=60.0))
    t.start()
    t.join()
    (p,) = rec.Phases()
    assert (p["start"], p["end"]) == (50.0, 60.0)
    assert p["thread"] == t.ident != threading.get_ident()

  def test_serving_loop_phases_nest_and_lie_in_order(self, tiny_lm):  # noqa: F811
    n = profile_lib.Startup().clock()
    eng = _Engine(tiny_lm)
    eng.Start()
    try:
      h = eng.Submit(np.arange(1, 6, dtype=np.int32), 3)
      h.Result(timeout=120)
    finally:
      eng.Stop()
    phases = _PhasesSince(n)
    names = [p["phase"] for p in phases]
    assert names == ["states", "build", "compile_step", "first_steps"]
    by = dict(zip(names, phases))
    assert by["states"]["parent"] == "build"
    assert _Inside(by["states"], by["build"])
    assert all(by[k]["parent"] is None
               for k in ("build", "compile_step", "first_steps"))
    assert (by["build"]["end"] <= by["compile_step"]["start"]
            <= by["compile_step"]["end"] <= by["first_steps"]["start"]
            < by["first_steps"]["end"])
    me = threading.get_ident()
    assert by["build"]["thread"] == by["compile_step"]["thread"] == me
    assert by["first_steps"]["thread"] != me           # the loop's thread
    # the named step programs lie inside compile_step, on the starter's thread
    rows = [r for r in profile_lib.Startup().Programs()
            if r["program"].startswith("serving/compile/")][-2:]
    assert [r["program"] for r in rows] == ["serving/compile/ragged",
                                            "serving/compile/feed"]
    zero = profile_lib.Startup().zero
    for r in rows:
      assert r["thread"] == me
      assert (by["compile_step"]["start"] <= zero + r["at_s"]
              <= zero + r["at_s"] + r["compile_wall_s"]
              <= by["compile_step"]["end"] + 1e-3)
    # the first token lies at first_steps' end, and the phase closes once
    first = min(r.first_token_ts for r in eng.trace.Requests().values())
    assert first <= by["first_steps"]["end"] <= first + 1.0
    assert eng._first_steps is False
    records = eng.Stats()["compile"]
    for k in RECORD_KEYS + ("compile_wall_s", "calls"):
      assert k in records["ragged"], k

  def test_an_engine_stepped_by_its_caller_opens_first_steps(self, tiny_lm):  # noqa: F811
    n = profile_lib.Startup().clock()
    eng = _Engine(tiny_lm)
    eng.RunBatch(np.ones((1, 3), np.int32), np.array([3]), 2)
    names = [p["phase"] for p in _PhasesSince(n)]
    assert names == ["states", "build", "first_steps"]   # built at dispatch
    assert _PhasesSince(n)[-1]["thread"] == threading.get_ident()

  def test_layout_is_a_phase_where_it_runs(self, tiny_lm):  # noqa: F811
    n = profile_lib.Startup().clock()
    eng = _Engine(tiny_lm)
    eng._Layout()
    eng._Layout()
    assert [p["phase"] for p in _PhasesSince(n)] == ["states", "build",
                                                     "layout"]

  @pytest.mark.parametrize("kw", [dict(async_infeed=False),
                                  dict(async_infeed=True)],
                           ids=["sync", "async"])
  def test_train_program_phases_nest_and_lie_in_order(self, tmp_path, kw):
    n = profile_lib.Startup().clock()
    n_loops = len(profile_lib.Startup().Loops())
    task, prog = _TrainProgram(str(tmp_path), **kw)
    state = task.CreateTrainState(jax.random.PRNGKey(0))
    prog.Compile(state)
    results = []
    for _ in range(3):
      state, res = prog.Run(state)
      results.append(res)
    prog.Flush()
    prog.Shutdown()
    phases = _PhasesSince(n)
    by = {p["phase"]: p for p in phases}
    want = ["build", "compile_step", "first_steps"]
    assert [p["phase"] for p in phases if p["phase"] in want] == want
    assert (by["build"]["end"] <= by["compile_step"]["start"]
            <= by["compile_step"]["end"] <= by["first_steps"]["start"]
            < by["first_steps"]["end"])
    if kw["async_infeed"]:
      assert _Inside(by["infeed"], by["first_steps"])
    # the named program's record, inside compile_step
    rec = prog.compile_records["step"]
    assert sum(rec[k] for k in KIND_KEYS) <= rec["compile_wall_s"] + 1e-6
    assert rec["trace_s"] > 0 and rec["thread"] == threading.get_ident()
    zero = profile_lib.Startup().zero
    assert (by["compile_step"]["start"] <= zero + rec["at_s"]
            <= by["compile_step"]["end"])
    # first_steps ends at the first loop's completion
    loops = profile_lib.Startup().Loops()[n_loops:]
    assert [u.kind for u in loops] == ["loop"] * 3
    assert by["first_steps"]["end"] == loops[0].done
    assert loops[0].done < loops[1].done < loops[2].done
    assert prog._first_steps is False

  def test_a_first_run_with_no_compile_before_it_is_the_named_program(
      self, tmp_path):
    # The process's record is bounded (its lists drop their oldest entries),
    # so a position in it means nothing on a worker that compiled a thousand
    # programs before this test: the test's own rows and events are those of
    # its two programs' names that began after it did. What a warm worker may
    # change is where an executable came from: a cached one reads `backend_s`
    # 0.0 with `fetch_s` > 0, which is why only their sum is held below.
    record = profile_lib.Startup()
    began = record.clock()
    mine = ("train/flops/step", "train/compile/step")
    task, prog = _TrainProgram(str(tmp_path))
    state = task.CreateTrainState(jax.random.PRNGKey(0))
    results = []
    for _ in range(2):
      state, res = prog.Run(state)
      results.append(res)
    prog.Flush()
    prog.Shutdown()
    rows = [r for r in record.Programs() if r["program"] in mine
            and record.zero + r["at_s"] >= began - 1e-3]
    # the lowering the flops are counted from, then the first dispatch: each
    # once, round the step function alone (no batch placement, no infeed)
    assert [r["program"] for r in rows] == list(mine)
    flops, step = rows
    assert flops["trace_s"] > 0 and flops["lower_s"] > 0
    assert flops["backend_s"] == flops["fetch_s"] == 0.0
    assert step["backend_s"] + step["fetch_s"] > 0
    events = [e for e in record.Events()
              if e.program in mine and e.start >= began]
    assert events and {e.unit.kind for e in events} == {"loop"}
    assert prog.compile_records == {}                  # an AOT record's place
    # the loop that compiled says so, beside host_overhead_s; the next is 0
    first, second = results[0], results[-1]
    assert first["compile_s"] > 0 and first["compile_fun_names"]
    assert first["compile_s"] >= flops["trace_s"] + step["backend_s"]
    assert second is first or second["compile_s"] == 0.0

  def test_phases_are_spans_on_the_host_plane(self, tiny_lm, tmp_path):  # noqa: F811
    logdir = str(tmp_path)
    jax.profiler.start_trace(logdir)
    try:
      eng = _Engine(tiny_lm)
      eng.Start()
      eng.Stop()
    finally:
      jax.profiler.stop_trace()
    names = set(_HostSpans(logdir))
    assert {"lingvo/setup/build", "lingvo/setup/states",
            "lingvo/setup/compile_step"} <= names
    assert "lingvo/setup/first_steps" not in names     # in the record only


# -- a step says that it compiled ---------------------------------------------


class TestStepCompile:

  def test_an_event_lands_in_the_open_step_and_in_no_other(self, tiny_lm):  # noqa: F811
    from lingvo_tpu.serving import engine as engine_lib
    eng = _Engine(tiny_lm)
    eng.RunBatch(np.ones((1, 3), np.int32), np.array([3]), 2)     # compiles
    before = len(eng.trace.Steps())
    rec = profile_lib.Startup()
    inner = engine_lib._StepSpans.To
    fed = []

    def _To(self, name):
      # one synthetic event inside the `build` of the second step from here
      inner(self, name)
      if name == "build":
        fed.append(name)
        if len(fed) == 2:
          rec.EventBegins(BACKEND)
          rec.EventEnds(BACKEND, "jit(late)")

    engine_lib._StepSpans.To = _To
    try:
      eng.RunBatch(np.ones((1, 3), np.int32), np.array([3]), 4)
    finally:
      engine_lib._StepSpans.To = inner
    steps = eng.trace.Steps()[before:]
    assert len(steps) >= 3
    hot = [s for s in steps if s.compile_s > 0]
    assert [s.step for s in hot] == [steps[1].step]
    assert hot[0].counters["compile_fun_names"] == ["jit(late)"]
    assert all(s.compile_s == 0.0 and "compile_fun_names" not in (
        s.counters or {}) for s in steps if s is not hot[0])
    ev = [e for e in rec.Events() if e.fun_name == "jit(late)"][-1]
    assert ev.unit.kind == "step"
    assert ev.unit.compile_s == hot[0].compile_s == pytest.approx(ev.self_s)
    # the step that built the programs says so too, by their names
    first = eng.trace.Steps()[0]
    assert first.compile_s > 0
    assert first.counters["compile_fun_names"]
    # an event outside any step's record belongs to none
    rec.EventBegins(TRACE)
    rec.EventEnds(TRACE, "outside")
    assert [e for e in rec.Events() if e.fun_name == "outside"][-1].unit is None

  def test_chrome_trace_and_the_report_show_it(self, tmp_path):
    clock = _FakeClock()
    rec = trace_lib.TraceRecorder(clock=clock)
    seg = [0.001] * len(trace_lib.STEP_SEGMENTS)
    for i in range(8):
      rec.StepDone(i, float(i), 0.001, seg)
    slow = list(seg)
    slow[trace_lib.STEP_SEGMENTS.index("dispatch")] = 3.0
    rec.StepDone(8, 8.0, 0.001, slow, counters={
        "compile_fun_names": ["jit(_Ragged)"]}, compile_s=2.9)
    rec.Submit(1, 3, 2)
    rec.Retire(1, "length")
    per_step = rec.ChromeTrace()["perStep"]
    assert per_step[-1]["compile_s"] == 2.9
    assert per_step[-1]["compile_fun_names"] == ["jit(_Ragged)"]
    assert per_step[0]["compile_s"] == 0.0
    assert per_step[0]["compile_fun_names"] == []
    path = str(tmp_path / "t.json")
    rec.Export(path)
    stalled = trace_report.StalledSteps(trace_report.LoadTrace(path))
    assert [r["step"] for r in stalled] == [8]
    assert stalled[0]["compile_ms"] == pytest.approx(2900.0)
    report = trace_report.Report(trace_report.LoadTrace(path))
    assert "stalled steps" in report and "jit(_Ragged)" in report

  def test_a_trace_from_before_the_key_reads_zero(self):
    steps = [{"step": i, "loop_s": 0.0, "span_s": 0.01 if i else 1.0}
             for i in range(6)]
    (row,) = trace_report.StalledSteps({"perStep": steps})
    assert row["step"] == 0 and row["compile_ms"] == 0.0
    assert row["compile_fun_names"] == []


# -- /statusz and the tool ----------------------------------------------------


class TestStatuszAndTool:

  def test_statusz_carries_the_record(self):
    srv = observe.StatusServer(0, registry=observe.MetricsRegistry("t"),
                               name="t")
    doc = srv.Statusz()
    observe_schema.ValidateStatusz(doc)
    startup = doc["startup"]
    assert set(startup) == {"phases", "programs", "other_programs",
                            "inside", "events_dropped"}
    assert set(startup["inside"]) == {"step", "loop"}
    json.dumps(startup)
    assert "startup" in observe_schema.STATUSZ_OPTIONAL

  def _Doc(self):
    rec, clock = _Record()
    with rec.Phase("build"):
      with rec.Phase("states"):
        _Event(rec, clock, TRACE, 1.0, "jit(InitPagedDecodeState)")
    clock.t += 0.5
    with rec.Phase("compile_step"), rec.Program("serving/compile/ragged"):
      _Event(rec, clock, TRACE, 3.0, "_Ragged")
      _Event(rec, clock, BACKEND, 2.0, "jit(_Ragged)",
             inside=[lambda: rec.CacheAnswered(True)])
    return rec.Document()

  def test_the_tool_prints_a_statusz_document(self, tmp_path, capsys):
    path = str(tmp_path / "statusz.json")
    with open(path, "w") as f:
      json.dump({"name": "serving", "startup": self._Doc()}, f)
    assert startup_report.main([path]) == 0
    out = capsys.readouterr().out
    for want in ("build", "  states", "(between)", "compile_step",
                 "serving/compile/ragged", "jit(InitPagedDecodeState)"):
      assert want in out, want
    row = next(ln for ln in out.splitlines()
               if ln.startswith("serving/compile/ragged"))
    assert row.split()[3:8] == ["3.000", "0.000", "0.000", "2.000", "True"]

  def test_the_tool_prints_a_runs_notes(self, tmp_path, capsys):
    tiling = {"setup_build_s": 0.5, "setup_step_trace_s": 3.0,
              "setup_step_lower_s": 0.0, "setup_step_compile_s": 2.0,
              "setup_other_programs_s": 1.0, "setup_first_steps_s": 0.25,
              "setup_unnamed_s": 4.0, "setup_s": 10.75, "overlap_s": 0.0}
    # standard output of a traced run: notes, then the line
    log = str(tmp_path / "run.log")
    with open(log, "w") as f:
      f.write(json.dumps({"note": "startup", "value": self._Doc()}) + "\n")
      f.write(json.dumps({"note": "startup_tiling", "value": tiling}) + "\n")
      f.write(json.dumps({"note": "window_compile", "value": {
          "compile_s": 1.5, "count": 1, "rows": [
              {"step": 7, "compile_s": 1.5, "fun_names": ["jit(late)"]}]}})
              + "\n")
      f.write(json.dumps({"correct": True, "metrics": {}}) + "\n")
    assert startup_report.main([log]) == 0
    out = capsys.readouterr().out
    assert "setup_unnamed_s" in out and "jit(late)" in out
    assert "serving/compile/ragged" in out
    # a notes file without the notes: the seven numbers of its line
    notes = str(tmp_path / "cell.notes.jsonl")
    with open(notes, "w") as f:
      f.write(json.dumps({"args": {}, "notes": {}, "line": {"metrics": {
          k: {"value": v, "unit": "s"} for k, v in tiling.items()
          if k not in ("setup_s", "overlap_s")}}}) + "\n")
    assert startup_report.main([notes]) == 0
    out = capsys.readouterr().out
    assert "setup_step_trace_s" in out and "3.000" in out
    assert "program " not in out

  def test_the_tool_says_when_a_file_holds_nothing(self, tmp_path, capsys):
    path = str(tmp_path / "empty.txt")
    with open(path, "w") as f:
      f.write("nothing here\n")
    assert startup_report.main([path]) == 1
    assert startup_report.main([]) == 2
