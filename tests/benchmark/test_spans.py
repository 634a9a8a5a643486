"""The readers of the program's own spans, step records and scope names
(benchmarks/harness/spans.py): the gap apportioning and the scope grouping on
hand-made intervals and on the small trace recorded on a TPU v5e from the
dense1b_serve_chat cell (a few engine steps;
benchmarks/tools/trace_spans_fixture.py cut it), every new reader on a run
of the shape the cells build, and the function that finds the traced run's
file."""

import json
import os

import pytest

from benchmarks.harness import spans
from benchmarks.harness import spec
from benchmarks.harness import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_TRACE = os.path.join(ROOT, "benchmarks", "data",
                      "trace_spans_small.json.gz")
SERVE_READERS = [b + s for b in ("step_span_ms", "step_host_ms",
                                 "step_host_share", "idle_unspanned_share",
                                 "busy_unscoped_share")
                 for s in (".lat", ".tput")]
STALL_READERS = [b + s for b in ("step_stall_share", "step_h2d_ms",
                                 "attend_block_fill")
                 for s in (".lat", ".tput")]
TRAIN_READERS = ["train_host_ms", "idle_unspanned_share",
                 "busy_unscoped_share"]


# -- where the traced run's file is -------------------------------------------


def test_trace_dir_is_where_run_py_writes_it(tmp_path):
  argv = ["--workload", "cell_a", "--seed", "7", "--out", str(tmp_path),
          "--trace", "1"]
  assert spans.TraceDir(argv) == str(tmp_path / "trace_cell_a")
  assert spans.TraceDir(["--workload", "c"]) == os.path.join(
      ROOT, "bench_out", "trace_c")


def test_trace_dir_needs_a_workload():
  with pytest.raises(ValueError, match="--workload"):
    spans.TraceDir(["--seed", "1"])


def test_trace_path_fails_loudly_without_a_trace(tmp_path):
  argv = ["--workload", "cell_a", "--out", str(tmp_path)]
  with pytest.raises(FileNotFoundError, match="trace_cell_a"):
    spans.TracePath(argv)


def test_trace_path_finds_the_newest_file(tmp_path):
  argv = ["--workload", "cell_a", "--out", str(tmp_path)]
  for stamp in ("2026_01_01", "2026_01_02"):
    d = tmp_path / "trace_cell_a" / "plugins" / "profile" / stamp
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"")
  assert spans.TracePath(argv) == str(
      tmp_path / "trace_cell_a" / "plugins" / "profile" / "2026_01_02"
      / "host.xplane.pb")


# -- idle time by span, hand-made ---------------------------------------------


def _Span(thread, name, start, end):
  return [thread, "lingvo/" + name, float(start), float(end - start), {}]


def test_leaf_intervals_give_a_parent_what_its_children_leave():
  sp = [_Span(0, "step", 0, 100), _Span(0, "admit", 10, 30),
        _Span(0, "build", 30, 50), _Span(0, "commit", 80, 100),
        _Span(1, "finalize", 5, 25)]
  leaves = spans.LeafIntervals(sp)
  assert leaves[0] == [(0.0, 10.0, "lingvo/step"),
                       (10.0, 30.0, "lingvo/admit"),
                       (30.0, 50.0, "lingvo/build"),
                       (50.0, 80.0, "lingvo/step"),
                       (80.0, 100.0, "lingvo/commit")]
  assert leaves[1] == [(5.0, 25.0, "lingvo/finalize")]


def test_leaf_intervals_nest_three_deep():
  sp = [_Span(0, "loop", 0, 100), _Span(0, "finalize", 20, 60),
        _Span(0, "device_wait", 30, 40)]
  assert spans.LeafIntervals(sp)[0] == [
      (0.0, 20.0, "lingvo/loop"), (20.0, 30.0, "lingvo/finalize"),
      (30.0, 40.0, "lingvo/device_wait"), (40.0, 60.0, "lingvo/finalize"),
      (60.0, 100.0, "lingvo/loop")]


def test_idle_goes_to_the_span_it_passed_under():
  # busy 10-40 and 60-90 of a window 0-100: idle 0-10, 40-60, 90-100
  busy = [[10.0, 40.0], [60.0, 90.0]]
  sp = [_Span(0, "step", 35, 95), _Span(0, "commit", 35, 50),
        _Span(0, "dispatch", 50, 58)]
  got = spans.IdleBySpan(busy, 0.0, 100.0, sp)
  assert got == pytest.approx({
      spans.UNSPANNED: 10.0 + 5.0,        # 0-10, and 95-100 after the step
      "lingvo/commit": 10.0,              # 40-50
      "lingvo/dispatch": 8.0,             # 50-58
      "lingvo/step": 2.0 + 5.0})          # 58-60 and 90-95: the step's own
  assert sum(got.values()) == pytest.approx(40.0)


def test_idle_under_two_threads_is_split_between_them():
  busy = [[0.0, 50.0]]
  sp = [_Span(0, "backpressure", 40, 100), _Span(1, "device_wait", 40, 80)]
  got = spans.IdleBySpan(busy, 0.0, 100.0, sp)
  assert got == pytest.approx({"lingvo/backpressure": 15.0 + 20.0,
                               "lingvo/device_wait": 15.0})


def test_idle_with_no_span_at_all_is_unspanned():
  got = spans.IdleBySpan([[20.0, 30.0]], 0.0, 100.0, [])
  assert got == pytest.approx({spans.UNSPANNED: 90.0})


def test_busy_outside_the_window_is_clipped():
  got = spans.IdleBySpan([[-50.0, 10.0], [90.0, 500.0]], 0.0, 100.0,
                         [_Span(0, "commit", 0, 100)])
  assert got == pytest.approx({"lingvo/commit": 80.0})


# -- device time by scope, hand-made ------------------------------------------


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_Step)/jit(main)/while/body/atten/dot_general", "atten"),
    ("jit(_RaggedStep)/while/body/atten/kv_write/scatter", "kv_write"),
    ("jit(_RaggedStep)/while/body/atten/ragged_attend/pallas_call",
     "ragged_attend"),
    ("jit(_Step)/transpose(jvp(while))/body/transpose(jvp(ffn))/mul", "ffn"),
    ("jit(_Step)/jvp(norm)/rsqrt", "norm"),
    ("jit(_Step)/optimizer_update/sqrt", "optimizer_update"),
    ("jit(_Step)/head_loss/reduce_sum", "head_loss"),
    ("jit(_RaggedStep)/head_sample/argmax", "head_sample"),
    ("jit(_Step)/embed/gather", "embed"),
    ("jit(_Step)/jit(main)/while", spans.UNSCOPED),
    ("jit(_Step)/layer_norm_variant/add", spans.UNSCOPED),
    ("jit(attend)/normalize/add", spans.UNSCOPED),
    ("", spans.UNSCOPED),
])
def test_scope_of_an_op_name(op_name, scope):
  assert spans.ScopeOf(op_name) == scope


def test_time_by_scope_counts_self_time_once():
  ops = [
      ["while %while.1 ()", 0.0, 100.0, "jit(f)/while"],
      ["fusion %fusion.1 bf16[8]", 0.0, 30.0, "jit(f)/while/body/atten/dot"],
      ["fusion %fusion.2 bf16[8]", 30.0, 50.0, "jit(f)/while/body/ffn/dot"],
      ["copy %copy.1 bf16[8]", 80.0, 10.0, ""],
      ["fusion %fusion.3 f32[8]", 100.0, 40.0, "jit(f)/optimizer_update/mul"],
  ]
  by_scope, largest = spans.TimeByScope(ops, 0.0, 140.0, top=2)
  assert by_scope == pytest.approx({
      "atten": 30.0, "ffn": 50.0, "optimizer_update": 40.0,
      spans.UNSCOPED: 10.0 + 10.0})       # the copy, and the while's own
  assert [(n, sc) for n, _, sc in largest] == [
      ("fusion %fusion.2 bf16[8]", "ffn"),
      ("fusion %fusion.3 f32[8]", "optimizer_update")]


def _Pb(*fields):
  """A protobuf message from (field number, value): ints as varints, bytes
  and str length-delimited."""
  def _V(x):
    out = bytearray()
    while True:
      out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
      x >>= 7
      if not x:
        return bytes(out)
  out = b""
  for num, v in fields:
    if isinstance(v, int):
      out += _V(num << 3) + _V(v)
    else:
      v = v.encode() if isinstance(v, str) else v
      out += _V(num << 3 | 2) + _V(len(v)) + v
  return out


def _Instr(name, op_name="", calls=()):
  fields = [(1, name), (2, "fusion")]
  if op_name:
    fields.append((7, _Pb((2, op_name))))
  fields += [(38, c) for c in calls]
  return (2, _Pb(*fields))


def test_op_names_come_from_the_hlo_proto_of_the_metadata_plane():
  fused = _Pb((1, "fused_computation.1"), (5, 11),
              _Instr("mul.1", "jit(f)/while/body/ffn/mul"),
              _Instr("add.1", "jit(f)/while/body/ffn/add"),
              _Instr("exp.1", "jit(f)/while/body/atten/exp"))
  entry = _Pb((1, "main"), (5, 12),
              _Instr("fusion.7", "jit(f)/atten/add"),
              _Instr("fusion.8", "jit(f)/while", calls=[11]),
              _Instr("fusion.9", "", calls=[11]),
              _Instr("copy.88"))
  hlo = _Pb((1, _Pb((1, "jit_f"), (3, fused), (3, entry))))
  meta = _Pb((1, 5), (2, "jit_f(5)"), (5, _Pb((1, 1), (6, hlo))))
  plane = _Pb((2, "/host:metadata"), (4, _Pb((1, 5), (2, meta))))
  other = _Pb((2, "/host:CPU"), (4, _Pb((1, 5), (2, meta))))
  got = spans.HloOpNames(_Pb((1, other), (1, plane)))
  assert set(got) == {"jit_f(5)"}
  names = got["jit_f(5)"]
  assert names["fusion.7"] == "jit(f)/atten/add"
  # an op_name with no scope in it: what the fusion holds decides
  assert spans.ScopeOf(names["fusion.8"]) == "ffn"
  assert spans.ScopeOf(names["fusion.9"]) == "ffn"
  assert names["copy.88"] == ""           # XLA's own copy carries nothing
  assert names["mul.1"] == "jit(f)/while/body/ffn/mul"


# -- the recorded trace -------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
  return spans.Load(_TRACE)


def _StepWindowOf(plain):
  return xplane.StepWindow({"/device:TPU:0": {
      xplane.MODULES_LINE: plain["modules"]}})


def test_recorded_trace_holds_whole_steps_and_their_spans(recorded):
  step = _StepWindowOf(recorded)
  assert step["name"].startswith("jit__RaggedStep(")
  assert step["count"] >= 2
  names = {n for _, n, *_ in recorded["spans"]}
  assert {"lingvo/serve/step", "lingvo/serve/h2d", "lingvo/serve/dispatch",
          "lingvo/serve/device_wait", "lingvo/serve/commit"} <= names
  steps = [a for _, n, _, _, a in recorded["spans"]
           if n == "lingvo/serve/step"]
  assert len(steps) >= step["count"]
  assert all({"step", "valid_tokens", "prefill_tokens", "rows"} <= set(a)
             for a in steps)
  assert len({t for t, *_ in recorded["spans"]}) == 1     # the engine loop


def test_recorded_idle_is_named_by_the_engines_spans(recorded):
  w0, w1 = _StepWindowOf(recorded)["window"]
  busy = spans._FirstDeviceBusy(recorded["ops"], w0, w1)
  by_span = spans.IdleBySpan(busy, w0, w1, recorded["spans"])
  idle = (w1 - w0) - sum(e - s for s, e in busy)
  assert 0.02 * (w1 - w0) < idle < 0.2 * (w1 - w0)
  assert sum(by_span.values()) == pytest.approx(idle, rel=1e-9)
  assert by_span.get(spans.UNSPANNED, 0.0) < 0.2 * idle
  assert all(k == spans.UNSPANNED or k.startswith("lingvo/serve/")
             for k in by_span)
  # the step's arguments are placed while the device waits for them
  assert max(by_span, key=by_span.get) == "lingvo/serve/h2d"


def test_recorded_ops_fall_under_the_serving_scopes(recorded):
  w0, w1 = _StepWindowOf(recorded)["window"]
  by_scope, largest = spans.TimeByScope(recorded["ops"], w0, w1)
  assert {"ragged_attend", "ffn", "atten", "kv_write", "norm",
          "head_sample", spans.UNSCOPED} <= set(by_scope)
  assert largest[0][2] == "ragged_attend"
  assert largest[0][0].startswith("custom-call:tpu_custom_call")
  assert len(largest) == 10
  scope_of = {n.split(" ")[1]: spans.ScopeOf(on)
              for n, _, _, on in recorded["ops"]}
  # XLA's own whole-pool copies carry no op_name at all; the scan's slices
  # of the stacked pool carry one with no block's name in it
  assert scope_of["%copy.88"] == spans.UNSCOPED
  assert scope_of["%bitcast_dynamic-update-slice_fusion.5"] == spans.UNSCOPED
  assert scope_of["%fusion.148"] == "kv_write"
  assert scope_of["%fusion.150"] == "ffn"
  op_names = {n.split(" ")[1]: on for n, _, _, on in recorded["ops"]}
  assert op_names["%copy.88"] == ""
  assert op_names["%ragged_attend.11"].endswith(
      "atten/ragged_attend/pallas_call")


# -- every new reader on a run of the shape the cells build -------------------


def _Recorder(n=12, t0=100.0, period=0.1, span=0.09):
  from lingvo_tpu.observe import trace as trace_lib
  rec = trace_lib.TraceRecorder()
  seg = [0.0] * len(trace_lib.STEP_SEGMENTS)
  # lock_wait admit build draft h2d dispatch device_wait lock_wait commit
  seg[0], seg[1], seg[2], seg[4], seg[5] = 0.001, 0.002, 0.003, 0.002, 0.002
  seg[6] = span - 0.016
  seg[7], seg[8] = 0.001, 0.005
  for i in range(n):
    rec.StepDone(i + 1, t0 + i * period, period - span, list(seg), 8, 0, 4)
  return rec


def _ServeRun(rec):
  return {"window": (100.0 - 1e-6, 100.0 + 12 * 0.1), "chips": 1,
          "trace_step": {"window": (0.0, 1.0), "count": 2}}


def _Read(name, run):
  read = spec.LayerMetricReader(name)
  assert read is not None, name
  return read(spec.RunData(run))


def test_step_readers_on_step_records(capsys):
  rec = _Recorder()
  run = _ServeRun(rec)
  for sfx in (".lat", ".tput"):
    assert _Read("step_span_ms" + sfx, run) == pytest.approx(90.0)
    # commit 5 + lock 1 | loop 10 | lock 1 admit 2 build 3 h2d 2 dispatch 2
    assert _Read("step_host_ms" + sfx, run) == pytest.approx(26.0)
    assert _Read("step_host_share" + sfx, run) == pytest.approx(26.0)
  notes = {n["note"]: n["value"] for n in map(
      json.loads, capsys.readouterr().out.strip().splitlines())}
  table = notes["step_host_phases_ms"]
  assert table["steps"] == 12
  assert table["commit"]["p50"] == pytest.approx(5.0)
  assert table["lock_wait"]["p50"] == pytest.approx(2.0)
  assert table["loop"]["p95"] == pytest.approx(10.0)
  assert table["span_over_phases"] == pytest.approx(1.0)
  cost = notes["step_trace_cost"]
  # a 1.2 s window lies inside the traced tail: nothing came before it
  assert set(cost) == {"during"} and cost["during"]["steps"] == 12
  del rec


def test_step_trace_cost_splits_the_window_at_the_traced_tail(capsys):
  rec = _Recorder(n=100)
  run = dict(_ServeRun(rec), window=(100.0 - 1e-6, 110.0))
  _Read("step_host_ms.lat", run)
  notes = {n["note"]: n["value"] for n in map(
      json.loads, capsys.readouterr().out.strip().splitlines())}
  cost = notes["step_trace_cost"]
  assert cost["before"]["steps"] + cost["during"]["steps"] in (99, 100)
  assert cost["during"]["steps"] == 60
  assert cost["before"]["step_span_ms"] == pytest.approx(90.0)
  assert cost["during"]["period_ms"] == pytest.approx(100.0)
  del rec


def test_steps_outside_the_window_are_left_out():
  rec = _Recorder()
  run = dict(_ServeRun(rec), window=(100.35, 100.75))
  steps = spans.StepRecords(run)
  assert [s.step for s in steps] == [4, 5, 6, 7]
  assert len(spans.HostGaps(steps)) == 3
  del rec


def test_a_program_without_step_records_gives_nothing_to_read(monkeypatch):
  from lingvo_tpu.observe import trace as trace_lib
  monkeypatch.delattr(trace_lib, "Live")
  run = {"window": (0.0, 1e9)}
  for name in SERVE_READERS[:6]:
    assert _Read(name, run) is None


def test_no_recorder_with_steps_in_the_window_gives_nothing_to_read():
  assert spans.StepRecords({"window": (-2.0, -1.0)}) is None
  assert _Read("step_span_ms.lat", {"window": (-2.0, -1.0)}) is None


def test_train_host_ms_reads_the_loop_results(capsys):
  results = [{"host_overhead_s": 0.030 + 0.001 * i, "infeed_wait_s": 0.010}
             for i in range(10)]
  run = {"loop_results": results, "intervals": [1.5] * 10}
  assert _Read("train_host_ms", run) == pytest.approx(24.5)
  note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
  assert note["note"] == "train_trace_cost"
  assert note["value"]["before"]["loops"] == 6        # ceil(6 / 1.5) traced
  assert note["value"]["during"]["loop_interval_ms"] == pytest.approx(1500.0)


def test_train_host_ms_without_the_counters_gives_nothing_to_read():
  assert _Read("train_host_ms", {"loop_results": [{"loss": 1.0}],
                                 "intervals": [1.0]}) is None


@pytest.fixture()
def traced_run(recorded, monkeypatch):
  """A run whose traced file is the recorded fixture."""
  monkeypatch.setattr(spans, "OfRun", lambda run: recorded)
  step = _StepWindowOf(recorded)
  return {"trace_step": step, "chips": 1}


@pytest.mark.parametrize("name", [n for n in SERVE_READERS + TRAIN_READERS
                                  if n.startswith(("idle_", "busy_"))])
def test_trace_readers_on_the_recorded_trace(name, traced_run, capsys):
  value = _Read(name, traced_run)
  assert 0.0 <= value < 100.0
  note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
  if name.startswith("idle_"):
    assert note["note"] == "idle_by_span"
    assert sum(note["value"]["by_span_s"].values()) == pytest.approx(
        note["value"]["idle_s"])
  else:
    assert note["note"] == "device_time_by_scope"
    assert len(note["value"]["largest_ops"]) == 10


def test_a_trace_without_spans_or_scopes_gives_nothing_to_read(
    recorded, monkeypatch):
  bare = {"spans": [], "modules": recorded["modules"],
          "ops": [[n, s, d, ""] for n, s, d, _ in recorded["ops"]]}
  monkeypatch.setattr(spans, "OfRun", lambda run: bare)
  run = {"trace_step": _StepWindowOf(recorded)}
  assert _Read("idle_unspanned_share", run) is None
  assert _Read("busy_unscoped_share.lat", run) is None


def _CellsOf(bench, end_to_end):
  """The cells that report an end-to-end metric, from BENCHMARK.json's own
  list: what every per-layer metric that moves it has to list. A later
  cell appends its name to both and these tests hold."""
  return next(m["workloads"] for m in bench["end_to_end"]
              if m["name"] == end_to_end)


def test_every_new_metric_has_its_reader_and_its_entry():
  bench = spec.LoadBenchmark()
  entries = {m["name"]: m for m in bench["per_layer"]}
  for name in set(SERVE_READERS + TRAIN_READERS + STALL_READERS):
    assert name in entries, name
    assert spec.LayerMetricReader(name) is not None
  train = _CellsOf(bench, "train_tok_s")
  assert entries["train_host_ms"]["workloads"] == train
  assert entries["idle_unspanned_share"]["workloads"] == train
  assert entries["step_host_share.lat"]["workloads"] == _CellsOf(
      bench, "itl_p95_ms")
  assert entries["step_host_share.tput"]["moves"] == "serve_tok_s"


# A reader of the engine, of the step or of the device finds something to
# read in every cell that reports the metric it moves, and lists them all. A
# reader of ONE kernel, or of what exists only across chips, finds it only in
# the cells whose program has that kernel or that mesh: it lists some of
# them, and the driver refuses a traced line that lacks a metric which lists
# its cell, so it may not list the rest. Which sort a reader is says the
# `layer` of its entry in BENCHMARK.json.
_LAYERS_OF_SOME_CELLS = ("kernels", "sharding")


def _ListsItsCells(bench, m) -> bool:
  """`m`'s list against the cells of the metric it moves, by its layer."""
  cells = _CellsOf(bench, m["moves"])
  if m["layer"] in _LAYERS_OF_SOME_CELLS:
    # some of them, each once, in their order there
    return bool(m["workloads"]) and m["workloads"] == [
        c for c in cells if c in m["workloads"]]
  return m["workloads"] == cells


def test_every_reader_lists_the_cells_of_the_metric_it_moves():
  """Rules, not names: every `.lat` metric moves `itl_p95_ms` and every
  `.tput` metric `serve_tok_s`; each of them, and every train reader, lists
  exactly the cells of the metric it moves, unless its layer is one whose
  readers find something in some cells only (`_LAYERS_OF_SOME_CELLS`): those
  list a part of them. `collective_exposed_share` is such a reader, and its
  cells are the four-chip ones."""
  bench = spec.LoadBenchmark()
  chips = {w["name"]: w["chips"] for w in bench["workloads"]}
  suffix = {".lat": "itl_p95_ms", ".tput": "serve_tok_s"}
  seen = {"itl_p95_ms": 0, "serve_tok_s": 0, "train_tok_s": 0}
  some = 0
  for m in bench["per_layer"]:
    ext = os.path.splitext(m["name"])[1]
    if ext in suffix:
      assert m["moves"] == suffix[ext], m["name"]
    elif m["moves"] != "train_tok_s":
      continue            # compile_s; a family's own metric of another kind
    assert _ListsItsCells(bench, m), (m["name"], m["layer"], m["workloads"])
    some += m["workloads"] != _CellsOf(bench, m["moves"])
    if m["name"] == "collective_exposed_share":
      assert m["workloads"] and all(chips[c] == 4 for c in m["workloads"])
    seen[m["moves"]] += 1
  assert all(seen[k] >= n for k, n in (
      ("itl_p95_ms", 17), ("serve_tok_s", 14), ("train_tok_s", 10))), seen
  # the rule is not idle: some kernel's reader lists a part of its cells
  assert some >= 1


def test_a_reader_of_the_engine_that_leaves_a_cell_out_is_caught():
  """The rule on a benchmark with one entry changed: a `serving engine`
  reader that drops a cell fails, a `kernels` reader that drops one holds,
  and one that lists a cell which does not report its metric fails."""
  bench = spec.LoadBenchmark()
  entries = {m["name"]: m for m in bench["per_layer"]}
  cells = _CellsOf(bench, "serve_tok_s")
  assert len(cells) >= 2
  engine = dict(entries["step_span_ms.tput"], workloads=cells[1:])
  assert engine["layer"] == "serving engine"
  assert not _ListsItsCells(bench, engine)
  kernel = dict(entries["ragged_attend_share.tput"], workloads=cells[1:])
  assert kernel["layer"] == "kernels"
  assert _ListsItsCells(bench, kernel)
  assert not _ListsItsCells(bench, dict(kernel, workloads=[]))
  assert not _ListsItsCells(bench, dict(
      kernel, workloads=cells[:1] + _CellsOf(bench, "itl_p95_ms")))


# -- where a window's seconds went (step_stall_share, step_h2d_ms) -------------


def _RecordedSteps(recorded, long_factor=None):
  """A recorder holding the recorded trace's three chat steps (as
  spans.StepsFromSpans reads them) and, with `long_factor`, one more after
  them: the last whole one over again with its device_wait that many times
  as long, which is what a stall of the device's queue looks like on
  record. The recorded trace holds no long step of its own."""
  from lingvo_tpu.observe import trace as trace_lib
  steps = spans.StepsFromSpans(recorded["spans"])
  rec = trace_lib.TraceRecorder()
  for s in steps:
    rec.StepDone(s.step, s.start_ts, s.loop_s, list(s.segments_s),
                 s.valid_tokens, s.prefill_tokens, s.rows)
  if long_factor:
    whole = steps[1]
    seg = list(whole.segments_s)
    seg[spans._DEVICE_WAIT] *= long_factor
    rec.StepDone(steps[-1].step + 1, steps[-1].end_ts + whole.loop_s,
                 whole.loop_s, seg, whole.valid_tokens, 0, whole.rows)
  return rec, steps


def test_recorded_spans_give_back_the_step_records(recorded):
  steps = spans.StepsFromSpans(recorded["spans"])
  assert [s.step for s in steps] == [273, 274, 275]
  whole = steps[1]            # the other two are cut by the recording's edges
  assert whole.span_s == pytest.approx(0.09193, abs=1e-4)
  assert set(whole.Phases()) >= {"lock_wait", "h2d", "device_wait", "commit"}
  assert whole.Phases()["h2d"] == pytest.approx(5.42e-3, rel=1e-3)
  assert 0 < whole.loop_s < 1e-3
  # the segments tile the step span but for the clock reads between them
  span = next(d for _, n, _, d, a in recorded["spans"]
              if n == "lingvo/serve/step" and a["step"] == 274)
  assert whole.span_s == pytest.approx(span * 1e-9, rel=2e-3)


@pytest.mark.parametrize("sfx", [".lat", ".tput"])
def test_h2d_and_stall_share_on_the_recorded_steps(recorded, sfx):
  rec, steps = _RecordedSteps(recorded)
  run = {"window": (steps[0].end_ts - 1e-6, steps[-1].end_ts + 1e-6)}
  assert _Read("step_h2d_ms" + sfx, run) == pytest.approx(5.42, abs=0.01)
  assert _Read("step_stall_share" + sfx, run) == 0.0     # three even steps
  del rec


@pytest.mark.parametrize("sfx", [".lat", ".tput"])
def test_a_long_step_after_the_recorded_ones_is_a_stall(recorded, sfx):
  rec, steps = _RecordedSteps(recorded, long_factor=3.0)
  run = {"window": (steps[0].end_ts - 1e-6, steps[-1].end_ts + 1.0)}
  records = spans.StepRecords(run)
  assert len(records) == 4
  periods = [p for _, p in spans.Periods(records)]
  assert periods[-1] > 2.0 * periods[0]
  share = _Read("step_stall_share" + sfx, run)
  assert share == pytest.approx(100.0 * periods[-1] / sum(periods))
  assert 55.0 < share < 65.0
  report = spans.WindowReport(records, *run["window"])
  assert report["stalls"] == 1 and report["slow_periods"] == 0
  assert report["stall_periods_s"] == pytest.approx(periods[-1])
  assert report["stall_excess_s"] == pytest.approx(
      periods[-1] - report["period_ms_median"] * 1e-3)
  (row,) = report["stalled_steps"]
  assert row["step"] == 276 and row["period_s"] == pytest.approx(
      periods[-1], abs=1e-4)
  # the phase that held the step is on record: the device's queue here
  assert max(row["phases_s"], key=row["phases_s"].get) == "device_wait"
  assert row["phases_s"]["device_wait"] == pytest.approx(3 * 0.08513, rel=1e-3)
  assert report["steps_x_median_s"] == pytest.approx(
      4 * report["period_ms_median"] * 1e-3)
  del rec


def test_window_report_counts_stalls_slow_steps_and_sixths():
  from lingvo_tpu.observe import trace as trace_lib
  seg = [0.0] * len(trace_lib.STEP_SEGMENTS)
  seg[4], seg[6] = 0.004, 0.056                       # h2d, device_wait
  steps, t = [], 0.0
  # 60 steps of 60 ms; step 20 waits 200 ms for the lock, step 40's h2d
  # takes 34 ms (a slow step, not a stall); from step 30 on h2d is 7 ms
  for i in range(60):
    s = list(seg)
    loop = 0.0
    if i == 20:
      s[0] = 0.2
    if i == 40:
      s[4] = 0.034
    if i >= 30 and i != 40:
      s[4], s[6] = 0.007, 0.053
    steps.append(trace_lib.StepTrace(i + 1, t, loop, tuple(s), 8, 0, 4))
    t += sum(s)
  report = spans.WindowReport(steps, 0.0, t)
  assert report["steps"] == 60
  assert report["period_ms_median"] == pytest.approx(60.0)
  assert report["stalls"] == 1 and report["slow_periods"] == 1
  assert report["stall_periods_s"] == pytest.approx(0.26)
  assert report["stall_excess_s"] == pytest.approx(0.2)
  assert report["slow_excess_s"] == pytest.approx(0.03)
  (row,) = report["stalled_steps"]
  assert row["step"] == 21 and row["at_s"] == pytest.approx(1.2)
  assert row["phases_s"]["lock_wait"] == pytest.approx(0.2)
  # what the steps at their usual length leave of the window is the loss
  assert report["window_s"] - report["steps_x_median_s"] == pytest.approx(
      0.23, abs=1e-6)
  assert report["h2d_ms_p50_by_sixth"] == pytest.approx(
      [4.0, 4.0, 4.0, 7.0, 7.0, 7.0])
  assert report["phases_ms"]["h2d"]["p50"] == pytest.approx(5.5)
  assert len(report["period_ms_p50_by_sixth"]) == 6


def test_window_report_keeps_the_longest_stalls_in_order_of_time():
  from lingvo_tpu.observe import trace as trace_lib
  seg = [0.0] * len(trace_lib.STEP_SEGMENTS)
  steps, t = [], 0.0
  for i in range(200):
    s = list(seg)
    s[6] = 0.05 if i % 2 else 0.15 + 0.001 * i      # every other one stalls
    steps.append(trace_lib.StepTrace(i + 1, t, 0.0, tuple(s), 1, 0, 1))
    t += s[6]
  report = spans.WindowReport(steps, 0.0, t, factor=2.0, keep=5)
  assert report["stalls"] == 99 and len(report["stalled_steps"]) == 5
  kept = [r["step"] for r in report["stalled_steps"]]
  assert kept == sorted(kept) == [191, 193, 195, 197, 199]
  assert spans.WindowReport(steps[:1], 0.0, 1.0) is None    # no period


def test_a_run_without_step_records_has_no_stall_metrics():
  run = {"window": (-2.0, -1.0)}
  for name in STALL_READERS[:4]:
    assert _Read(name, run) is None


# -- attend_block_fill --------------------------------------------------------


def _AttendRun(per_step, bq=128):
  """Steps 0.1 s apart; per_step: (blocks, queries) each step added."""
  records, attend, blocks, queries = [], [], 0, 0
  for i, (b, q) in enumerate(per_step):
    blocks, queries = blocks + b, queries + q
    records.append((100.0 + 0.1 * i, 0.09, i + 1, 0))
    attend.append((blocks, queries))
  return {"window": (99.95, 100.0 + 0.1 * len(per_step)),
          "step_records": records, "attend_blocks": attend, "attend_bq": bq}


@pytest.mark.parametrize("sfx", [".lat", ".tput"])
def test_attend_block_fill_is_queries_over_block_rows(sfx):
  # decode-only steps: 14 rows, one query in a block of 128 each
  run = _AttendRun([(14, 14)] * 10)
  assert _Read("attend_block_fill" + sfx, run) == pytest.approx(100 / 128)
  # a chunk step: a dozen decode rows beside a 512-token chunk's four blocks
  chunk = _AttendRun([(14, 14)] + [(16, 12 + 512)] * 4)
  assert _Read("attend_block_fill" + sfx, chunk) == pytest.approx(
      100.0 * 524 / (16 * 128))
  # the window's first completion is the base: its own counts stay out
  mixed = _AttendRun([(1000, 1000), (14, 14), (16, 524)])
  assert _Read("attend_block_fill" + sfx, mixed) == pytest.approx(
      100.0 * 538 / (30 * 128))


def test_attend_block_fill_without_the_counters_gives_nothing_to_read():
  assert _Read("attend_block_fill.tput", _AttendRun([(0, 0)] * 5)) is None
  assert _Read("attend_block_fill.tput", _AttendRun([(3, 3)] * 5, bq=0)) is None
  run = _AttendRun([(3, 3)] * 5)
  del run["attend_blocks"]            # a run of the harness before the metric
  with pytest.raises(spec.NothingToRead):
    _Read("attend_block_fill.lat", run)


def test_stall_metrics_have_their_entries():
  bench = spec.LoadBenchmark()
  entries = {m["name"]: m for m in bench["per_layer"]}
  for base, layer, unit in (("step_stall_share", "serving engine", "%"),
                            ("step_h2d_ms", "serving engine", "ms"),
                            ("attend_block_fill", "kernels", "%")):
    lat, tput = entries[base + ".lat"], entries[base + ".tput"]
    assert lat["layer"] == tput["layer"] == layer
    assert lat["unit"] == tput["unit"] == unit
    assert (lat["moves"], tput["moves"]) == ("itl_p95_ms", "serve_tok_s")
    # all the cells of the metric it moves, or for a kernel's reader those
    # whose program runs the kernel (`brumby14b_serve_longwrite` runs no
    # ragged attend: `attend_block_fill.tput` finds nothing to read there)
    assert _ListsItsCells(bench, lat) and _ListsItsCells(bench, tput)
