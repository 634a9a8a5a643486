"""Milliseconds a step by block (benchmarks/harness/scope_ms.py): the
attribution on hand-made op lists under a hand-made tree, the readers on a
run of the shape the cells build, on the recorded chat trace under the
program's own tree, and the entries of the metrics that read it."""

import json
import os

import pytest

from benchmarks.harness import scope_ms
from benchmarks.harness import spans
from benchmarks.harness import spec
from benchmarks.harness import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_TRACE = os.path.join(ROOT, "benchmarks", "data",
                      "trace_spans_small.json.gz")
# a tree of the program's shape: blocks, children, a grandchild
TREE = {
    "atten": (None, "a block"),
    "qkv_proj": ("atten", "a child"),
    "kv_write": ("atten", "a child with a kernel named after it"),
    "kv_layout": ("kv_write", "a grandchild"),
    "ragged_attend": ("atten", "a child round a kernel"),
    "ffn": (None, "a block with children no op of this trace lies in"),
    "moe_route": ("ffn", "a child"),
    "optimizer_update": (None, "a block with no op"),
    "layer_scan": (None, "the scan's own ops, the gathers of its slices"),
}
KERNEL = "custom-call:tpu_custom_call"
NEW = ["ffn_ms.lat", "ffn_ms.tput", "atten_dense_ms.lat",
       "atten_dense_ms.tput", "kv_write_ms.lat", "kv_write_ms.tput",
       "moe_dispatch_ms", "ffn_ms", "atten_dense_ms", "optimizer_ms",
       "unscoped_collective_ms"]


def _Op(opcode, name, start_us, dur_us, op_name):
  return [f"{opcode} %{name} f32[8]", start_us * 1e3, dur_us * 1e3, op_name]


# two steps of 100 us each, ops back to back; times in microseconds
OPS = [
    _Op("fusion", "fusion.1", 0, 10, "jit(f)/while/body/atten/qkv_proj/dot"),
    _Op("fusion", "fusion.2", 10, 6, "jit(f)/while/body/atten/add"),
    _Op("fusion", "fusion.3", 16, 4,
        "jit(f)/while/body/atten/kv_write/kv_layout/gather"),
    _Op(KERNEL, "kv_write.5", 20, 8,
        "jit(f)/while/body/atten/kv_write/jit(_W)/kv_write/pallas_call"),
    _Op(KERNEL, "ragged_attend.7", 28, 30,
        "jit(f)/while/body/atten/ragged_attend/pallas_call"),
    _Op("fusion", "fusion.4", 58, 2,
        "jit(f)/while/body/atten/ragged_attend/concatenate"),
    _Op(KERNEL, "atten.9", 60, 5, "jit(f)/while/body/atten/pallas_call"),
    _Op("fusion", "fusion.6", 65, 20, "jit(f)/transpose(jvp(ffn))/mul"),
    _Op("all-gather-start", "all-gather-start.1", 85, 3, ""),
    _Op("all-reduce", "all-reduce.2", 88, 2, ""),
    _Op("copy", "copy.3", 90, 4, ""),
    _Op("fusion", "fusion_bitcast_dynamic-update-slice_fusion.8", 94, 3, ""),
    _Op("fusion", "fusion.9", 97, 3, "jit(f)/layer_norm_variant/add"),
    _Op("all-gather", "all-gather.7", 110, 2,
        "jit(f)/jvp(layer_scan)/while/body/dynamic_slice"),
    _Op("fusion", "dynamic-slice_bitcast_fusion.5", 112, 2,
        "jit(f)/jvp(layer_scan)/while/body/squeeze"),
    _Op("fusion", "async-collective-start.3", 114, 1, ""),
    # the second step: one op, the rest of it idle
    _Op("fusion", "fusion.1", 100, 10, "jit(f)/while/body/atten/qkv_proj/dot"),
]
W = (0.0, 200e3)


@pytest.fixture()
def tree():
  return scope_ms.Tree(OPS, *W, steps=2, scopes=TREE)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/while/body/atten/qkv_proj/dot_general", "qkv_proj"),
    ("jit(f)/atten/kv_write/kv_layout/gather", "kv_layout"),
    ("jit(f)/atten/kv_write/jit(_W)/kv_write/pallas_call", "kv_write"),
    ("jit(f)/transpose(jvp(atten))/transpose(jvp(qkv_proj))/dot", "qkv_proj"),
    ("jit(f)/jvp(ffn)/mul", "ffn"),
    ("jit(f)/ffn_out/mul", scope_ms.UNSCOPED),
    ("jit(f)/not_an_atten/mul", scope_ms.UNSCOPED),
    ("", scope_ms.UNSCOPED),
])
def test_the_innermost_declared_scope_wins(op_name, scope):
  assert scope_ms.ScopeOf(op_name, scope_ms._Pattern(TREE)) == scope


@pytest.mark.parametrize("short, cls", [
    ("all-gather-start %all-gather-start.1 bf16[8]", "all-gather"),
    ("all-gather-done %all-gather-done.1 bf16[8]", "all-gather"),
    ("all-reduce %all-reduce.2 f32[8]", "all-reduce"),
    ("reduce-scatter %reduce-scatter.2 f32[8]", "reduce-scatter"),
    ("copy %copy.88 bf16[24,194]", "copy"),
    ("copy-start %copy-start.1 bf16[24]", "copy"),
    ("fusion %copy_fusion.3 bf16[24]", "copy"),
    ("dynamic-update-slice %dynamic-update-slice.1 f32[8]",
     "dynamic-update-slice"),
    ("fusion %fusion_bitcast_dynamic-update-slice_fusion.31 f32[13,8192]",
     "dynamic-update-slice"),
    ("fusion %async-collective-start.3 bf16[1,8]", "collective"),
    ("collective-permute %collective-permute.1 f32[8]", "collective"),
    ("fusion %dynamic-slice_bitcast_fusion.22 bf16[8,1024]", "dynamic-slice"),
    ("fusion %fusion.12 f32[8]", "other"),
    ("custom-call:tpu_custom_call %atten.3 f32[8]", "other"),
])
def test_an_unscoped_op_is_classed_by_its_opcode(short, cls):
  assert scope_ms.OpcodeClass(short) == cls


@pytest.mark.parametrize("scope, self_ms, ms, kernel_ms", [
    ("qkv_proj", 0.010, 0.010, 0.0),       # 2 x 10 us over 2 steps
    ("atten", 0.0055, 0.0375, 0.0025),     # add 6 + flash 5, halved
    ("kv_write", 0.004, 0.006, 0.004),     # the kernel; with kv_layout's 2
    ("kv_layout", 0.002, 0.002, 0.0),
    ("ragged_attend", 0.016, 0.016, 0.015),
    ("ffn", 0.010, 0.010, 0.0),
    ("moe_route", 0.0, 0.0, 0.0),
    ("optimizer_update", 0.0, 0.0, 0.0),   # declared, no op: 0.0, not absent
    ("layer_scan", 0.002, 0.002, 0.0),     # a gather and a slice of 2 us each
])
def test_self_time_rolls_up_by_the_declared_parent(tree, scope, self_ms, ms,
                                                   kernel_ms):
  got = tree["scopes"][scope]
  assert got["self_ms"] == pytest.approx(self_ms)
  assert got["ms"] == pytest.approx(ms)
  assert got["kernel_ms"] == pytest.approx(kernel_ms)
  assert got["parent"] == TREE[scope][0]
  assert got["collective_ms"] == (0.001 if scope == "layer_scan" else 0.0)


def test_unscoped_time_splits_by_opcode_and_everything_adds_up(tree):
  assert tree["unscoped_by_opcode"] == pytest.approx({
      "all-gather": 0.0015, "all-reduce": 0.001, "reduce-scatter": 0.0,
      "collective": 0.0005, "copy": 0.002, "dynamic-update-slice": 0.0015,
      "dynamic-slice": 0.0, "other": 0.0015})
  assert tree["unscoped_ms"] == pytest.approx(0.008)
  blocks = sum(v["ms"] for v in tree["scopes"].values()
               if v["parent"] is None)
  assert blocks + tree["unscoped_ms"] == pytest.approx(tree["sum_ms"])
  assert tree["sum_ms"] == pytest.approx(0.0575)       # 115 us over 2 steps
  assert tree["unscoped_ops"][0] == [
      "copy_copy.3_f32_8_", pytest.approx(0.002), "copy"]


def test_the_largest_unnamed_is_a_parents_own_time_or_unscoped_other(tree):
  """`atten` has children with ops, so its own 6 us of adds (its flash
  kernel apart) is unnamed, and so is the 2 us beside `ragged_attend`'s
  kernel; `ffn`'s children hold nothing here, so it is a leaf and named;
  `kv_write`'s own time is all its kernel's."""
  assert tree["largest_unnamed"] == {"name": "atten (own)",
                                     "ms": pytest.approx(0.003)}
  no_adds = [op for op in OPS if op[0].split()[1] != "%fusion.2"]
  assert scope_ms.Tree(no_adds, *W, steps=2, scopes=TREE)[
      "largest_unnamed"] == {"name": "_unscoped/other",
                             "ms": pytest.approx(0.0015)}
  no_other = [op for op in no_adds if op[0].split()[1] != "%fusion.9"]
  assert scope_ms.Tree(no_other, *W, steps=2, scopes=TREE)[
      "largest_unnamed"] == {"name": "ragged_attend (own)",
                             "ms": pytest.approx(0.001)}
  lone = scope_ms.Tree([op for op in OPS if "%fusion.9 " in op[0]], *W,
                       steps=1, scopes=TREE)
  assert lone["largest_unnamed"] == {"name": "_unscoped/other",
                                     "ms": pytest.approx(0.003)}


def test_an_op_across_the_windows_edge_is_clipped(tree):
  half = scope_ms.Tree(OPS, 5e3, 200e3, steps=2, scopes=TREE)
  assert half["scopes"]["qkv_proj"]["self_ms"] == pytest.approx(0.0075)
  assert half["sum_ms"] == pytest.approx(tree["sum_ms"] - 0.0025)


def test_a_while_keeps_only_what_its_body_leaves():
  ops = [_Op("while", "while.1", 0, 100, ""),
         _Op("fusion", "fusion.1", 10, 60, "jit(f)/while/body/ffn/dot")]
  got = scope_ms.Tree(ops, 0.0, 100e3, steps=1, scopes=TREE)
  assert got["scopes"]["ffn"]["ms"] == pytest.approx(0.060)
  assert got["unscoped_by_opcode"]["other"] == pytest.approx(0.040)


# -- the readers --------------------------------------------------------------


def _Read(name, run):
  return spec.LayerMetricReader(name)(spec.RunData(run))


@pytest.fixture()
def run(monkeypatch):
  monkeypatch.setattr(spans, "OfRun", lambda run: {"ops": OPS})
  monkeypatch.setattr(scope_ms, "Registry", lambda: TREE)
  scope_ms._memo.clear()
  yield {"trace_step": {"window": W, "count": 2, "mean_s": 100e-6}}
  scope_ms._memo.clear()


@pytest.mark.parametrize("name, ms", [
    ("ffn_ms", 0.010), ("ffn_ms.lat", 0.010), ("ffn_ms.tput", 0.010),
    # atten 37.5 less kv_write 6, ragged_attend 16 and the flash kernel 2.5;
    # the unscoped gather, all-reduce and async fusion 3, the scan's gather 1
    ("atten_dense_ms", 0.013), ("atten_dense_ms.lat", 0.013),
    ("atten_dense_ms.tput", 0.013),
    ("kv_write_ms.lat", 0.006), ("kv_write_ms.tput", 0.006),
    ("moe_dispatch_ms", 0.0),              # declared, no op in the cell
    ("optimizer_ms", 0.0),
    ("unscoped_collective_ms", 0.004),
])
def test_a_reader_gives_milliseconds_a_step(name, ms, run):
  assert _Read(name, run) == pytest.approx(ms)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_registry_gives_nothing_to_read(
    name, run, monkeypatch):
  monkeypatch.setattr(scope_ms, "Registry", lambda: None)
  assert _Read(name, run) is None


def test_registry_is_the_programs_own_tree():
  from lingvo_tpu.observe import schema
  assert scope_ms.Registry() is schema.DEVICE_SCOPES


def test_the_note_is_printed_once_a_run_and_adds_up(run, capsys):
  for name in NEW:
    _Read(name, run)
  notes = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
  assert [n["note"] for n in notes] == ["device_ms_by_scope"]
  v = notes[0]["value"]
  assert v["step_ms"] == pytest.approx(0.1) and v["steps"] == 2
  assert v["busy_ms"] == pytest.approx(v["sum_ms"])
  assert set(v["scopes"]) == {"atten", "qkv_proj", "kv_write", "kv_layout",
                              "ragged_attend", "ffn", "layer_scan"}
  assert v["largest_unnamed"]["share_of_step"] == pytest.approx(3.0)


def test_the_recorded_chat_trace_under_the_programs_tree(monkeypatch):
  """Three steps of dense1b_serve_chat recorded on a v5e before the finer
  scopes: the blocks are there, their children read 0.0, and with the
  unscoped classes they add up to the device's busy time."""
  recorded = spans.Load(_TRACE)
  monkeypatch.setattr(spans, "OfRun", lambda run: recorded)
  scope_ms._memo.clear()
  step = xplane.StepWindow({"/device:TPU:0": {
      xplane.MODULES_LINE: recorded["modules"]}})
  run = {"trace_step": step}
  tree = scope_ms.ByScope(run)
  scope_ms._memo.clear()
  assert tree["sum_ms"] == pytest.approx(tree["busy_ms"], rel=1e-6)
  assert tree["busy_ms"] <= tree["step_ms"]
  sc = tree["scopes"]
  assert sc["ragged_attend"]["kernel_ms"] == pytest.approx(
      sc["ragged_attend"]["ms"], rel=1e-4)     # the kernel and a slice
  assert sc["atten"]["ms"] > sc["kv_write"]["ms"] > 0.0
  assert sc["qkv_proj"]["ms"] == sc["moe_route"]["ms"] == 0.0
  assert _Read("ffn_ms.lat", run) == pytest.approx(sc["ffn"]["ms"])
  assert _Read("atten_dense_ms.lat", run) == pytest.approx(
      sc["atten"]["self_ms"])
  assert tree["unscoped_by_opcode"]["copy"] > 0.0


# -- the entries --------------------------------------------------------------


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_has_its_reader_and_its_entry(name):
  bench = spec.LoadBenchmark()
  entry = next(m for m in bench["per_layer"] if m["name"] == name)
  assert spec.LayerMetricReader(name) is not None
  assert (entry["unit"], entry["better"], entry["source"]) == (
      "ms", "lower", "device_trace")
  cells = lambda e2e: next(m["workloads"] for m in bench["end_to_end"]
                           if m["name"] == e2e)
  ext = os.path.splitext(name)[1]
  if ext == ".lat":
    assert entry["moves"] == "itl_p95_ms"
    assert entry["workloads"] == cells("itl_p95_ms")
  elif ext == ".tput":
    assert entry["moves"] == "serve_tok_s"
    assert entry["workloads"] == cells("serve_tok_s")
  elif name == "moe_dispatch_ms":
    assert entry["moves"] == "serve_tok_s"
    assert entry["workloads"] == ["smallthinker21b_serve_mixed"]
  else:
    assert entry["moves"] == "train_tok_s"
    assert entry["workloads"] == cells("train_tok_s")


def test_the_new_entries_stand_at_the_end_of_the_list():
  """The end of the list as PR 42 left it: PR 42 appended them; every PR since appends behind them. So they are
  held to being there, each once, in the order they were added in among
  themselves (a metric is appended, never moved), not to the list's end."""
  names = [m["name"] for m in spec.LoadBenchmark()["per_layer"]]
  assert [n for n in names if n in NEW] == NEW
