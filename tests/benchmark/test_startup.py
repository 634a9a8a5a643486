"""The set-up metrics that read the program's start-up record
(benchmarks/harness/startup.py): setup_build_s, setup_step_trace_s,
setup_step_lower_s, setup_step_compile_s, setup_other_programs_s,
setup_first_steps_s, setup_unnamed_s, and window_compile_s.

- each reader on a hand-made record; the seven add up to run["setup_s"],
  none negative, two threads that compile at once counted once;
- a program without the record gives every reader nothing to read;
- the rule that test_spec pins to one name, held by rule: every per-layer
  metric that moves `setup_s` lists its cells, all of them;
- the readers called on the record that a `--rehearse` run of one serve and
  one train cell leaves in its process.
"""

import collections
import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import spec
from benchmarks.harness import startup

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SETUP_METRICS = ("setup_build_s", "setup_step_trace_s", "setup_step_lower_s",
                 "setup_step_compile_s", "setup_other_programs_s",
                 "setup_first_steps_s", "setup_unnamed_s")
NEW_METRICS = SETUP_METRICS + ("window_compile_s",)

Loop = collections.namedtuple("Loop", "done")


def _Record(events=(), phases=(), programs=(), loops=(), zero=0.0):
  """A hand-made start-up record: the program's own class, its lists filled
  by hand."""
  from lingvo_tpu.observe import profile
  rec = profile.StartupRecord(zero=zero)
  rec._events.extend(events)
  rec._phases.extend(phases)
  rec._programs.extend(programs)
  rec._loops.extend(loops)
  return rec


def _E(kind, start, end, program=None, thread=1, fun_name="f", self_s=None):
  from lingvo_tpu.observe import profile
  return profile.CompileEvent(
      kind, start, end, end - start if self_s is None else self_s, fun_name,
      thread, program, None)


def _P(phase, start, end, parent=None, thread=1):
  return {"phase": phase, "start": start, "end": end, "thread": thread,
          "parent": parent}


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
  monkeypatch.setattr(startup, "_noted", set())
  monkeypatch.setattr(startup, "_tilings", {})


def _Serve(monkeypatch, record, window=(100.0, 130.0), setup_s=40.0):
  monkeypatch.setattr(startup, "Record", lambda: record)
  return spec.RunData({"window": window, "setup_s": setup_s})


def _Read(name, run):
  return spec.LayerMetricReader(name)(run)


# A set-up of 40 s that ends at t = 100 (zero at 58; 2 s of bring-up left
# out): build 60-64 with an eager op's events inside; compile_step 65-80
# with `ragged` (trace 65-72 holding an inner trace 66-67, lower 72-74, a
# fetch 74-79) and `feed` (trace 79-79.5); first_steps 80-86 on another
# thread with a late eager compile 81-82 on it; a harness jit 90-93.
_RAGGED, _FEED = "serving/compile/ragged", "serving/compile/feed"
_EVENTS = [
    _E("trace", 61.0, 61.5, fun_name="jit(InitPagedDecodeState)"),
    _E("compile", 61.5, 63.0, fun_name="jit(InitPagedDecodeState)"),
    _E("trace", 66.0, 67.0, _RAGGED, fun_name="_where"),
    _E("trace", 65.0, 72.0, _RAGGED, fun_name="_Ragged", self_s=6.0),
    _E("lower", 72.0, 74.0, _RAGGED, fun_name="jit(_Ragged)"),
    _E("fetch", 74.0, 79.0, _RAGGED, fun_name="jit(_Ragged)"),
    _E("trace", 79.0, 79.5, _FEED, fun_name="_FeedTokens"),
    _E("compile", 81.0, 82.0, thread=2, fun_name="jit(gather)"),
    _E("lower", 90.0, 93.0, fun_name="jit(_Reference)"),
    _E("compile", 101.0, 104.0, fun_name="jit(late)"),      # in the window
]
_PHASES = [_P("states", 60.5, 63.5, "build"), _P("build", 60.0, 64.0),
           _P("compile_step", 65.0, 80.0),
           _P("first_steps", 80.0, 86.0, thread=2),
           _P("build", 120.0, 121.0)]                       # in the window
_PROGRAMS = [
    {"program": _RAGGED, "at_s": 7.0, "compile_wall_s": 14.0, "trace_s": 7.0,
     "lower_s": 2.0, "backend_s": 0.0, "fetch_s": 5.0, "cache_hit": True,
     "thread": 1},
    {"program": _FEED, "at_s": 21.0, "compile_wall_s": 0.6, "trace_s": 0.5,
     "lower_s": 0.0, "backend_s": 0.0, "fetch_s": 0.0, "cache_hit": None,
     "thread": 1},
    {"program": "probe/late", "at_s": 50.0, "compile_wall_s": 1.0,
     "trace_s": 1.0, "lower_s": 0.0, "backend_s": 0.0, "fetch_s": 0.0, "cache_hit": None,
     "thread": 1}]
_WANT = {"setup_build_s": 2.0,              # 4 s less the 2 s that compiled
         "setup_step_trace_s": 7.5, "setup_step_lower_s": 2.0,
         "setup_step_compile_s": 5.0,
         "setup_other_programs_s": 2.0 + 1.0 + 3.0,
         "setup_first_steps_s": 5.0,        # 6 s less the late compile
         "setup_unnamed_s": 40.0 - 27.5}


def _Hand():
  return _Record(_EVENTS, _PHASES, _PROGRAMS, zero=58.0)


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_a_reader_on_a_hand_made_record(name, monkeypatch):
  run = _Serve(monkeypatch, _Hand())
  assert _Read(name, run) == pytest.approx(_WANT[name])


def test_the_seven_add_up_to_setup_and_none_is_negative(monkeypatch, capsys):
  run = _Serve(monkeypatch, _Hand())
  got = {n: _Read(n, run) for n in SETUP_METRICS}
  assert sum(got.values()) == pytest.approx(run["setup_s"])
  assert all(v >= 0 for v in got.values()), got
  notes = {}
  for ln in capsys.readouterr().out.splitlines():
    obj = json.loads(ln)
    assert obj["note"] not in notes, "a note is printed once"
    notes[obj["note"]] = obj["value"]
  tiling = notes["startup_tiling"]
  assert {k: tiling[k] for k in SETUP_METRICS} == pytest.approx(got)
  assert tiling["setup_s"] == 40.0 and tiling["overlap_s"] == 0.0
  # each named program's row, those that ended before the window
  assert [r["program"] for r in notes["startup_step_programs"]] == [
      _RAGGED, _FEED]
  assert notes["startup_step_programs"][0]["cache_hit"] is True
  other = notes["startup_other_programs"]
  assert other["events"] == 4 and other["seconds"] == pytest.approx(6.0)
  assert other["top"][0] == ["jit(_Reference)", 3.0, 1, {"lower": 3.0}]
  assert notes["startup"]["programs"] == notes["startup_step_programs"]
  assert [p["phase"] for p in notes["startup"]["phases"]] == [
      "states", "build", "compile_step", "first_steps"]   # not the window's


def test_two_threads_that_compile_at_once_are_counted_once(monkeypatch,
                                                           capsys):
  """The loop's thread fetches the step program (a draft source's engine)
  while the starter's thread compiles a program of its own, 4 s on each, and
  a third thread traces for 1 s inside both: 9 s of self time over 5 s of
  wall. The 4 s counted more than once come off the last event part."""
  record = _Record([
      _E("fetch", 10.0, 14.0, _RAGGED, thread=2),
      _E("compile", 11.0, 15.0, thread=1, fun_name="jit(mine)"),
      _E("trace", 12.0, 13.0, thread=3, fun_name="third")],
      [_P("first_steps", 9.0, 16.0, thread=2)])
  run = _Serve(monkeypatch, record, window=(20.0, 50.0), setup_s=20.0)
  got = {n: _Read(n, run) for n in SETUP_METRICS}
  assert got["setup_step_compile_s"] == pytest.approx(4.0)
  assert got["setup_other_programs_s"] == pytest.approx(1.0)
  assert got["setup_first_steps_s"] == pytest.approx(2.0)
  assert sum(got.values()) == pytest.approx(20.0)
  notes = {json.loads(ln)["note"]: json.loads(ln)["value"]
           for ln in capsys.readouterr().out.splitlines()}
  assert notes["startup_tiling"]["overlap_s"] == pytest.approx(4.0)


def test_the_parts_are_the_records_own_self_seconds(monkeypatch):
  """One nesting rule, the listener's: an event's `self_s` is what counts,
  not its length (an outer trace of 10 s that held 7 s of inner events the
  record no longer keeps is 3 s)."""
  record = _Record([_E("trace", 10.0, 20.0, _RAGGED, self_s=3.0)],
                   [_P("compile_step", 10.0, 20.0)])
  run = _Serve(monkeypatch, record, window=(30.0, 60.0), setup_s=25.0)
  assert _Read("setup_step_trace_s", run) == pytest.approx(3.0)
  assert _Read("setup_unnamed_s", run) == pytest.approx(22.0)


def test_what_compiled_in_the_ramp_is_said_beside_the_tiling(monkeypatch,
                                                             capsys):
  """Set-up ends with the warm-up; the window opens behind the ramp. `run`
  has the steps the harness recorded after the warm-up, the program's
  records the steps before: an event that ended between the last warm-up
  step and the window counts in its part AND under `ramp_compile_s`."""
  from lingvo_tpu.observe import trace as trace_lib
  _Steps(monkeypatch, {})     # step i from 1000 + i on, a millisecond a phase
  record = _Record([
      _E("trace", 990.0, 992.0, _RAGGED),                     # set-up
      _E("compile", 1003.5, 1003.9, fun_name="jit(warm)"),    # warm-up
      _E("lower", 1004.5, 1004.75, fun_name="jit(late)"),     # the ramp
      _E("fetch", 1006.0, 1006.5, _RAGGED),                   # the ramp
      _E("compile", 1008.0, 1009.0, fun_name="jit(window)")])
  # the harness's records behind its warm-up: (t_end, duration, ...) from
  # step 5 on; the window opens at step 7
  run = _Serve(monkeypatch, record, window=(1007.5, 1037.5), setup_s=20.0)
  run["step_records"] = [(1005.007 + i, 0.007) for i in range(5)]
  assert startup.RampStart(run) == pytest.approx(
      1004.0 + 0.001 * len(trace_lib.STEP_SEGMENTS))      # step 4's end
  assert _Read("setup_other_programs_s", run) == pytest.approx(0.65)
  assert _Read("setup_unnamed_s", run) == pytest.approx(20.0 - 3.15)
  notes = {json.loads(ln)["note"]: json.loads(ln)["value"]
           for ln in capsys.readouterr().out.splitlines()}
  assert notes["startup_tiling"]["ramp_compile_s"] == pytest.approx({
      "step_trace": 0.0, "step_lower": 0.0, "step_compile": 0.5,
      "other_programs": 0.25})
  # records that no longer reach back: the first recorded step's start
  monkeypatch.setattr(trace_lib, "Live", lambda: [])
  assert startup.RampStart(run) == pytest.approx(1005.0)
  # a train run's set-up ends where its window starts
  assert startup.RampStart(spec.RunData({"intervals": [1.0]})) is None


def test_interval_arithmetic():
  assert startup.Union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [[0, 2], [3, 4]]
  assert startup.Minus([[0, 10]], [[1, 2], [4, 5], [9, 12]]) == [
      [0, 1], [2, 4], [5, 9]]
  assert startup.Minus([[0, 1], [2, 3]], [[0, 5]]) == []
  assert startup.Minus([[0, 1]], []) == [[0, 1]]
  assert startup.Length([[0, 1], [2, 4.5]]) == 3.5


def test_a_train_runs_set_up_ends_before_its_windows_loops(monkeypatch):
  """Five loops completed, the window is the last three: set-up's events
  stop counting at the second loop's completion."""
  record = _Record(
      [_E("trace", 10.0, 12.0, "train/compile/loop"),
       _E("compile", 12.0, 19.0, "train/compile/loop"),
       _E("trace", 30.5, 31.0, fun_name="in the window")],
      [_P("build", 5.0, 6.0), _P("first_steps", 9.0, 21.0, thread=2)],
      loops=[Loop(done) for done in (21.0, 30.0, 40.0, 50.0, 60.0)])
  monkeypatch.setattr(startup, "Record", lambda: record)
  run = spec.RunData({"intervals": [10.0, 10.0, 10.0], "setup_s": 28.0,
                      "loop_results": [{"compile_s": 0.0}] * 3})
  assert startup.WindowStart(run, record) == 30.0
  got = {n: _Read(n, run) for n in SETUP_METRICS}
  assert got == pytest.approx({
      "setup_build_s": 1.0, "setup_step_trace_s": 2.0,
      "setup_step_lower_s": 0.0, "setup_step_compile_s": 7.0,
      "setup_other_programs_s": 0.0, "setup_first_steps_s": 3.0,
      "setup_unnamed_s": 15.0})
  # fewer completions than the window's loops and one more: nothing to read
  short = _Record(loops=[Loop(1.0)] * 3)
  assert startup.WindowStart(run, short) is None
  monkeypatch.setattr(startup, "Record", lambda: short)
  monkeypatch.setattr(startup, "_tilings", {})
  assert _Read("setup_build_s", run) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_record_gives_nothing_to_read(name,
                                                            monkeypatch):
  """The parent of the PR that brought the record: no Startup() in
  observe.profile, no compile_s on a step record or in a loop's result."""
  from lingvo_tpu.observe import profile
  monkeypatch.delattr(profile, "Startup")
  assert startup.Record() is None
  run = spec.RunData({"window": (1e12, 1e12 + 1), "setup_s": 10.0})
  assert _Read(name, run) is None
  train = spec.RunData({"intervals": [1.0], "setup_s": 10.0,
                        "loop_results": [{"host_overhead_s": 0.1}]})
  assert _Read(name, train) is None


def test_a_run_that_holds_no_setup_leaves_only_the_remainder_out(monkeypatch):
  """Through spec.ReadLayerMetrics, as run.py calls the readers: six parts
  are the record's own; the seventh needs run["setup_s"]."""
  monkeypatch.setattr(startup, "Record", _Hand)
  cell = spec.Cell(spec.LoadBenchmark(), "dense1b_serve_docs")
  cell["per_layer"] = [m for m in cell["per_layer"]
                       if m["name"] in SETUP_METRICS]
  assert len(cell["per_layer"]) == 7
  got = spec.ReadLayerMetrics(cell, {"window": (100.0, 130.0)})
  assert got == {k: {"value": pytest.approx(_WANT[k]), "unit": "s"}
                 for k in SETUP_METRICS[:-1]}


# -- window_compile_s ----------------------------------------------------------


def _Steps(monkeypatch, compile_by_step):
  from lingvo_tpu.observe import trace as trace_lib
  rec = trace_lib.TraceRecorder()
  seg = [0.001] * len(trace_lib.STEP_SEGMENTS)
  for i in range(10):
    s = compile_by_step.get(i, 0.0)
    rec.StepDone(i, 1000.0 + i, 0.0, seg, counters=(
        {"compile_fun_names": ["jit(late)"]} if s else None), compile_s=s)
  monkeypatch.setattr(trace_lib, "Live", lambda: [rec])
  return rec


def test_window_compile_reads_the_steps_inside_the_window(monkeypatch, capsys):
  _Steps(monkeypatch, {1: 5.0, 4: 1.5, 6: 0.25, 9: 7.0})
  run = spec.RunData({"window": (1002.5, 1008.5)})
  assert _Read("window_compile_s", run) == pytest.approx(1.75)
  (note,) = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
  assert note["note"] == "window_compile" and note["value"]["count"] == 2
  assert note["value"]["rows"] == [
      {"step": 4, "compile_s": 1.5, "fun_names": ["jit(late)"]},
      {"step": 6, "compile_s": 0.25, "fun_names": ["jit(late)"]}]


def test_window_compile_is_zero_where_nothing_compiled(monkeypatch, capsys):
  _Steps(monkeypatch, {})
  run = spec.RunData({"window": (1002.5, 1008.5)})
  value = _Read("window_compile_s", run)
  assert value == 0.0 and isinstance(value, float)
  (note,) = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
  assert note["value"] == {"compile_s": 0.0, "rows": [], "count": 0}


def test_window_compile_of_a_train_run_reads_the_loop_results(capsys):
  run = spec.RunData({"intervals": [1.0] * 3, "loop_results": [
      {"compile_s": 0.0, "at_step": 8},
      {"compile_s": 2.5, "at_step": 12, "compile_fun_names": ["jit(_Loop)"]},
      {"compile_s": 0.0, "at_step": 16}]})
  assert startup.WindowCompile(run) == pytest.approx(2.5)
  (note,) = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
  assert note["value"]["rows"] == [
      {"at_step": 12, "compile_s": 2.5, "fun_names": ["jit(_Loop)"]}]


# -- the entries ---------------------------------------------------------------


def test_the_eight_entries_are_appended_in_their_order():
  bench = spec.LoadBenchmark()
  cells = [w["name"] for w in bench["workloads"]]
  tail = bench["per_layer"][-8:]
  assert [m["name"] for m in tail] == list(NEW_METRICS)
  for m in tail[:7]:
    assert m["moves"] == "setup_s" and m["layer"] == "entry point"
    assert m["workloads"] == cells
    assert (m["unit"], m["better"]) == ("s", "lower")
    assert m["source"] == ("program_span" if m["name"] in (
        "setup_build_s", "setup_first_steps_s") else "program_counter")
  last = tail[7]
  e2e = {m["name"]: m for m in bench["end_to_end"]}
  assert last["moves"] == "serve_tok_s" and last["layer"] == "serving engine"
  assert last["workloads"] == e2e["serve_tok_s"]["workloads"]
  assert (last["unit"], last["better"], last["source"]) == (
      "s", "lower", "program_counter")
  for m in tail:
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert not m["name"].endswith((".lat", ".tput"))
    assert spec.LayerMetricReader(m["name"]) is not None


def test_every_metric_that_moves_setup_lists_all_its_cells():
  """By rule, not by name: `setup_s` is every cell's, so a metric that moves
  it and lists nothing would have to be reported by every later cell. Each
  lists the cells that report it; the ones that read the program's own
  record (a number in every cell, 0.0 where nothing happened) list them
  all, as `compile_s` does."""
  bench = spec.LoadBenchmark()
  cells = [w["name"] for w in bench["workloads"]]
  mine = [m for m in bench["per_layer"] if m["moves"] == "setup_s"]
  assert len(mine) >= 8
  for m in mine:
    assert m["workloads"], m["name"]
    assert m["workloads"] == [c for c in cells if c in m["workloads"]]
    assert m["layer"] == "entry point", m["name"]
    assert m["workloads"] == cells, m["name"]


# -- on the record a rehearsal leaves -------------------------------------------

_PROBE = r"""
import json, os, sys
# two cores are enough for a tiny cell, and leave the rest to the tests that
# run beside this one (test_rehearsal.py counts steps in a 2 s window)
if hasattr(os, "sched_setaffinity"):
  os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[:2])
sys.path.insert(0, {root!r})
from benchmarks import run as run_py
from benchmarks.harness import spec
cell, out = sys.argv[1], sys.argv[2]
if "train" in cell:
  from benchmarks.harness import train_cell as cell_lib
else:
  from benchmarks.harness import serve_cell as cell_lib
kept, inner = {{}}, cell_lib.Run
def _Run(ctx):
  got = inner(ctx)
  kept["run"] = got["run"]
  return got
cell_lib.Run = _Run
rc = run_py.main(["--workload", cell, "--seed", "3000000019", "--seconds",
                  "2", "--trace", "0", "--rehearse", "--out", out])
run = spec.RunData(kept["run"])
values = {{n: spec.LayerMetricReader(n)(run) for n in {names!r}}}
from lingvo_tpu.observe import profile
print(json.dumps({{"rc": rc, "setup_s": run["setup_s"], "values": values,
                  "programs": profile.Startup().Programs()}}))
"""


@pytest.mark.parametrize("cell,programs", [
    ("dense1b_serve_docs", ["serving/compile/ragged", "serving/compile/feed"]),
    # no Compile() in the train cells: the lowering the flops are counted
    # from, then the first dispatch
    ("dense1b_train_packed", ["train/flops/step", "train/compile/step"])])
def test_the_readers_on_the_record_a_rehearsal_leaves(cell, programs,
                                                      tmp_path):
  env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
  env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
  done = subprocess.run(
      [sys.executable, "-c", _PROBE.format(root=ROOT, names=NEW_METRICS),
       cell, str(tmp_path)], cwd=ROOT, env=env, capture_output=True,
      text=True, timeout=600)
  assert done.returncode == 0, done.stderr[-2000:]
  lines = [json.loads(ln) for ln in done.stdout.strip().splitlines()]
  got = lines[-1]
  assert got["rc"] == 0
  values = got["values"]
  assert all(isinstance(values[n], float) for n in NEW_METRICS), values
  assert all(values[n] >= 0 for n in NEW_METRICS), values
  assert sum(values[n] for n in SETUP_METRICS) == pytest.approx(
      got["setup_s"], abs=1e-6)
  # a cold CPU run traces, lowers and compiles its step program, builds
  # something and takes its first steps; nothing compiles in its window
  for n in ("setup_build_s", "setup_step_trace_s", "setup_step_lower_s",
            "setup_step_compile_s", "setup_other_programs_s",
            "setup_first_steps_s", "setup_unnamed_s"):
    assert values[n] > 0, n
  assert values["window_compile_s"] == 0.0
  assert [r["program"] for r in got["programs"]][:len(programs)] == programs
  notes = {x["note"]: x["value"] for x in lines if "note" in x}
  assert [r["program"] for r in notes["startup_step_programs"]] == programs
  assert notes["startup_tiling"]["overlap_s"] >= 0.0
  assert notes["window_compile"]["count"] == 0
