"""The trace reduction, on the small trace recorded on a TPU v5e from the
dense1b_train_packed cell (two train steps; benchmarks/tools/trace_fixture.py
cut it) and on hand-made events."""

import gzip
import json
import os

import pytest

from benchmarks.harness import flops
from benchmarks.harness import hybrid_cost
from benchmarks.harness import layer_lib
from benchmarks.harness import peaks
from benchmarks.harness import spec
from benchmarks.harness import xplane

_TRACE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "data",
    "trace_train_small.json.gz")


_SERVE_TRACE = os.path.join(os.path.dirname(_TRACE),
                            "trace_spans_small.json.gz")


@pytest.fixture(scope="module")
def trace():
  return xplane.Load(_TRACE)


@pytest.fixture(scope="module")
def serve_trace():
  """The recorded chat steps (benchmarks/tools/trace_spans_fixture.py keeps
  the first device's ops and modules) in the plain form Reduce reads."""
  with gzip.open(_SERVE_TRACE, "rt") as f:
    plain = json.load(f)
  return {"/device:TPU:0": {
      xplane.OPS_LINE: [[n, s, d] for n, s, d, _ in plain["ops"]],
      xplane.MODULES_LINE: plain["modules"]}}


def test_recorded_trace_holds_two_whole_steps(trace):
  step = xplane.StepWindow(trace)
  assert step["name"].startswith("jit__Step(")
  assert step["count"] == 2
  assert step["mean_s"] == pytest.approx(0.3418, rel=1e-3)


def test_recorded_trace_reduces_to_busy_kernel_and_ops(trace):
  step = xplane.StepWindow(trace)
  red = xplane.Reduce(trace, window=step["window"])
  assert red["devices"] == 1
  assert red["window_s"] == pytest.approx(0.690831, rel=1e-5)
  assert red["busy_s"] == pytest.approx(0.683547, rel=1e-5)
  assert 0.0 < 1.0 - red["busy_s"] / red["window_s"] < 0.02
  # the four flash kernels (forward, its remat twin, two backward) of 13
  # layers over two steps; fusions that merely read %custom-call.N operands
  # are not kernels
  assert red["kernel_s"] == pytest.approx(0.058450, rel=1e-4)
  assert red["collective_exposed_s"] == 0.0
  names = [n for n, _ in red["ops"]]
  assert len(names) == 10
  assert names[0].startswith("fusion_bitcast_dynamic-update-slice_fusion.31")
  assert sum(n.startswith("custom-call:tpu_custom_call_") for n in names) == 2
  times = [t for _, t in red["ops"]]
  assert times == sorted(times, reverse=True)
  assert red["idle_gaps"] and all(t > 0 for _, t in red["idle_gaps"])
  idle = red["window_s"] - red["busy_s"]
  assert sum(t for _, t in red["idle_gaps"]) == pytest.approx(idle, rel=1e-6)


def test_self_time_takes_the_body_out_of_a_while():
  evs = [["while", 0, 100], ["a", 10, 30], ["b", 40, 50], ["c", 120, 20]]
  got = {n: d for n, _, d in xplane.SelfTimes(evs)}
  assert got == {"while": 20, "a": 30, "b": 50, "c": 20}


def test_union_merges_overlap_and_drops_empty():
  assert xplane.Union([(5, 9), (0, 3), (2, 4), (7, 7)]) == [[0, 4], [5, 9]]


def test_short_name_reads_the_opcode_not_the_operands():
  fusion = ("%fusion.430 = bf16[8,1024,2048,1]{2,1,3,0:T(8,128)(2,1)} "
            "fusion(bf16[2048,16,128]{2,0,1} %custom-call.26), kind=kLoop")
  kernel = ("%checkpoint.20 = (bf16[128,1024,128]{2,1,0:T(8,128)(2,1)}, "
            "bf16[128,1024,128]{2,1,0}) custom-call(bf16[128,1024,128]{2,1,0} "
            '%bitcast.562), custom_call_target="tpu_custom_call", x={}')
  start = "%all-reduce-start.3 = f32[8]{0} all-reduce-start(f32[8]{0} %p)"
  assert xplane.Opcode(xplane.ShortName(fusion)) == "fusion"
  assert xplane.Opcode(xplane.ShortName(kernel)) == xplane.KERNEL
  assert xplane.COLLECTIVE.match(xplane.Opcode(xplane.ShortName(start)))
  assert xplane.ShortName("not hlo") == "not hlo"


def test_collectives_and_gaps_on_hand_made_events():
  trace = {
      "/device:TPU:0": {
          "XLA Ops": [["fusion %f f32[8]", 0, 40],
                      ["all-reduce %ar f32[8]", 40, 20],
                      ["all-gather-done %ag f32[8]", 100, 10]],
          "XLA Modules": [["jit_step(1)", 0, 60], ["jit_step(1)", 100, 10]]},
      "/device:TPU:1": {
          "XLA Ops": [["fusion %f f32[8]", 0, 110]],
          "XLA Modules": [["jit_step(1)", 0, 110]]},
      "/host:CPU": {"main": [["Run", 0, 200], ["np.asarray", 58, 44]]},
  }
  red = xplane.Reduce(trace, window=(0, 110))
  assert red["devices"] == 2
  assert red["busy_s"] == pytest.approx((70 + 110) / 2 * 1e-9)
  assert red["collective_exposed_s"] == pytest.approx(30 / 2 * 1e-9)
  assert red["idle_gaps"][0][0] == "np.asarray"   # the innermost host event
  assert red["idle_gaps"][0][1] == pytest.approx(40e-9)
  with pytest.raises(ValueError):
    xplane.Reduce({"/device:TPU:0": {"Steps": [["1", 0, 1]]}})


# -- the kernels' time by the scope each op is named after --------------------


def test_scope_of_op_reads_the_name_xla_gave_the_op():
  assert xplane.ScopeOfOp(
      "custom-call:tpu_custom_call %ragged_attend.15 bf16[672,16,128]"
  ) == "ragged_attend"
  assert xplane.ScopeOfOp(
      "custom-call:tpu_custom_call %atten.37 (bf16[128,1024,128]") == "atten"
  assert xplane.ScopeOfOp("custom-call:tpu_custom_call %moe_ffn f32[8]"
                          ) == "moe_ffn"
  assert xplane.ScopeOfOp("fusion %fusion.183 bf16[544,8192]") == "fusion"
  assert xplane.ScopeOfOp("not hlo") == ""


def test_time_by_scope_sums_to_kernel_s_on_the_recorded_train_trace(trace):
  red = xplane.Reduce(trace, window=xplane.StepWindow(trace)["window"])
  by_scope = red["kernel_s_by_scope"]
  # recorded before the program named its blocks (PR 24): the four flash
  # kernels carry jax.checkpoint's names, not `atten`
  assert len(by_scope) >= 1 and all(t > 0 for t in by_scope.values())
  assert sum(by_scope.values()) == pytest.approx(red["kernel_s"], rel=1e-12)
  assert red["per_device"][0]["kernel_s_by_scope"].keys() == by_scope.keys()
  assert xplane.KernelSeconds(red, "ragged_attend") is None


def test_one_kind_of_kernel_is_kernel_s_to_the_last_bit(serve_trace):
  red = xplane.Reduce(serve_trace,
                      window=xplane.StepWindow(serve_trace)["window"])
  assert red["kernel_s"] > 0
  assert red["kernel_s_by_scope"] == {"ragged_attend": red["kernel_s"]}
  assert xplane.KernelSeconds(red, "ragged_attend") == red["kernel_s"]
  assert xplane.KernelSeconds(red, "atten", "ragged_attend") == red["kernel_s"]
  assert xplane.KernelSeconds(red, "atten", "shard_map") is None


def _WithASecondKernel(serve_trace):
  """The recorded steps with a second kernel beside the ragged attend in
  every layer: the last quarter of each ragged attend's time is a grouped
  matmul over experts instead."""
  ops = []
  for n, s, d in serve_trace["/device:TPU:0"][xplane.OPS_LINE]:
    if xplane.Opcode(n) == xplane.KERNEL:
      ops.append([n, s, 0.75 * d])
      ops.append(["custom-call:tpu_custom_call %moe_ffn.3 bf16[544,2560]",
                  s + 0.75 * d, 0.25 * d])
    else:
      ops.append([n, s, d])
  return {"/device:TPU:0": dict(serve_trace["/device:TPU:0"],
                                **{xplane.OPS_LINE: ops})}


def test_a_second_kernel_is_not_counted_as_ragged_attend(serve_trace):
  step = xplane.StepWindow(serve_trace)
  alone = xplane.Reduce(serve_trace, window=step["window"])
  both = xplane.Reduce(_WithASecondKernel(serve_trace), window=step["window"])
  assert both["kernel_s"] == pytest.approx(alone["kernel_s"], rel=1e-9)
  assert set(both["kernel_s_by_scope"]) == {"ragged_attend", "moe_ffn"}
  assert both["kernel_s_by_scope"]["ragged_attend"] == pytest.approx(
      0.75 * alone["kernel_s"], rel=1e-9)
  assert sum(both["kernel_s_by_scope"].values()) == pytest.approx(
      both["kernel_s"], rel=1e-12)


def test_by_scope_is_a_mean_over_devices():
  kernel = "custom-call:tpu_custom_call %atten.{} f32[8]"
  trace = {
      "/device:TPU:0": {"XLA Ops": [[kernel.format(1), 0, 40],
                                    [kernel.format(2), 50, 20],
                                    ["custom-call:tpu_custom_call %moe f32[8]",
                                     80, 10]]},
      "/device:TPU:1": {"XLA Ops": [[kernel.format(1), 0, 60]]}}
  red = xplane.Reduce(trace, window=(0, 100))
  assert red["kernel_s_by_scope"] == {"atten": pytest.approx(60e-9),
                                      "moe": pytest.approx(5e-9)}
  assert red["kernel_s"] == pytest.approx(65e-9)


# -- the roofline readers read their own kernel's time -------------------------


def _Read(name, run):
  return spec.LayerMetricReader(name)(spec.RunData(run))


def _ServeRun(serve_trace, **sizes):
  step = xplane.StepWindow(serve_trace)
  red = xplane.Reduce(serve_trace, window=step["window"])
  rows = [[(1, 300 + 7 * i) for i in range(20)] + [(256, 700)]
          for _ in range(step["count"] + 2)]
  return {"trace": red, "trace_step": step, "step_rows": rows,
          "packed_t": 544, "peak": peaks.PeakOf("TPU v5 lite"),
          "sizes": dict(num_heads=16, dim_per_head=128, num_layers=24,
                        **sizes)}


@pytest.mark.parametrize("sfx", [".lat", ".tput"])
def test_ragged_readers_give_what_the_parents_formula_gives(serve_trace, sfx,
                                                            capsys):
  """On a trace with one kind of kernel and a file that states neither KV
  heads nor windows: the parent's arithmetic on `kernel_s`, to the digit."""
  run = _ServeRun(serve_trace)
  red, n = run["trace"], run["trace_step"]["count"]
  assert _Read("ragged_attend_share" + sfx, run) == (
      100.0 * red["kernel_s"] / red["busy_s"])
  ops = nbytes = 0.0
  for rows in run["step_rows"][-n:]:
    o, b = flops.RaggedAttendStepCost(rows, 544, 16, 128, 24)
    ops, nbytes = ops + o, nbytes + b
  want, bound = flops.RooflineShare(ops, nbytes, red["kernel_s"], run["peak"])
  assert _Read("ragged_attend_roofline" + sfx, run) == want
  note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
  assert note["value"] == {"bound": bound, "ops": ops, "bytes": nbytes,
                           "steps": n, "kernel_s": red["kernel_s"]}


@pytest.mark.parametrize("sfx", [".lat", ".tput"])
def test_ragged_roofline_counts_the_steps_the_trace_holds(serve_trace, sfx,
                                                          capsys):
  """The trace stops where the window closes and the engine runs on while
  the probe waits: a closed loop then drains, and the last steps the
  recorder saw hold a few decode rows and no chunk. The reader counts the n
  steps that were done when the window closed (`hybrid_cost.TracedStepRows`,
  the one copy), not the last n recorded."""
  run = _ServeRun(serve_trace)
  n = run["trace_step"]["count"]
  in_trace = run["step_rows"][:n]
  drain = [[(1, 900 + i) for i in range(3)] for _ in range(4 * n)]
  # a step every 0.1 s from 100.0; the window closes behind the n-th
  run["step_rows"] = in_trace + drain
  run["step_records"] = [(100.0 + 0.1 * i, 0.09, i + 1, 0)
                         for i in range(len(run["step_rows"]))]
  run["window"] = (99.95, 100.0 + 0.1 * (n - 1) + 0.01)
  assert hybrid_cost.TracedStepRows(run, n) == in_trace

  def _Share(step_rows):
    ops = nbytes = 0.0
    for rows in step_rows:
      o, b = flops.RaggedAttendStepCost(rows, 544, 16, 128, 24)
      ops, nbytes = ops + o, nbytes + b
    return flops.RooflineShare(ops, nbytes, run["trace"]["kernel_s"],
                               run["peak"])[0]

  got = _Read("ragged_attend_roofline" + sfx, run)
  assert got == _Share(in_trace)
  assert got > 2 * _Share(run["step_rows"][-n:])   # what the parent read
  note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
  assert note["note"] == "ragged_attend_roofline"
  assert note["value"]["steps"] == n
  assert note["value"]["kernel_s"] == run["trace"]["kernel_s"]


def test_ragged_roofline_counts_kv_heads_and_windows_from_the_file(
    serve_trace):
  full = _Read("ragged_attend_roofline.tput", _ServeRun(serve_trace))
  grouped = _Read("ragged_attend_roofline.tput",
                  _ServeRun(serve_trace, num_kv_heads=2))
  windowed = _Read("ragged_attend_roofline.tput", _ServeRun(
      serve_trace, num_kv_heads=2, attention_windows=[128] * 18 + [0] * 6))
  assert windowed < grouped < full
  # memory-bound, and K and V are nearly all the bytes: an eighth of the
  # heads is little more than an eighth of the share
  assert 0.125 < grouped / full < 0.2


def test_readers_beside_a_second_kernel_and_without_their_own(serve_trace):
  alone = _ServeRun(serve_trace)
  both = _ServeRun(_WithASecondKernel(serve_trace))
  # the second kernel's time is busy time, and not ragged attend's
  assert _Read("ragged_attend_roofline.lat", both) == pytest.approx(
      _Read("ragged_attend_roofline.lat", alone) / 0.75, rel=1e-9)
  assert _Read("ragged_attend_share.lat", both) == pytest.approx(
      0.75 * _Read("ragged_attend_share.lat", alone), rel=1e-9)
  # a trace with kernels of another kind only: nothing to read, not 0
  other = dict(alone, trace=dict(alone["trace"],
                                 kernel_s_by_scope={"moe_ffn": 0.1}))
  for name in ("ragged_attend_roofline.tput", "ragged_attend_share.tput"):
    assert _Read(name, other) is None
  assert layer_lib.RAGGED_KERNEL == "ragged_attend"


def test_flash_reader_reads_the_atten_kernels(trace, capsys):
  step = xplane.StepWindow(trace)
  red = xplane.Reduce(trace, window=step["window"])
  run = {"trace": red, "trace_step": step, "chips": 1, "layers": 13,
         "peak": peaks.PeakOf("TPU v5 lite"),
         "sizes": dict(batch_size=8, seq_len=1024, num_heads=16,
                       dim_per_head=128)}
  # the recorded kernels carry jax.checkpoint's names (before PR 24): no
  # `atten` kernel, nothing to read
  assert _Read("flash_attn_roofline", run) is None
  # as the program names them today, all of them `atten`: the parent's
  # arithmetic on kernel_s
  run["trace"] = dict(red, kernel_s_by_scope={"atten": red["kernel_s"]})
  ops, nbytes = flops.FlashTrainStepCost(8, 1024, 16, 128, 13)
  want, _ = flops.RooflineShare(ops * 2, nbytes * 2, red["kernel_s"],
                                run["peak"])
  assert _Read("flash_attn_roofline", run) == want
  assert want == pytest.approx(27.1, abs=0.2)      # ledger, PR 33: 27.15
  # on a mesh they are named after `shard_map` (the kernel is called inside
  # a mapped body with no scope of its own): the same kernels, the same time
  run["trace"] = dict(red, kernel_s_by_scope={"shard_map": red["kernel_s"]})
  assert _Read("flash_attn_roofline", run) == want
  # another kernel beside them is not flash attention's
  run["trace"] = dict(red, kernel_s_by_scope={
      "atten": red["kernel_s"], "moe_ffn": 1.0})
  assert _Read("flash_attn_roofline", run) == want
  note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
  assert note["value"]["kernel_s_per_step"] == red["kernel_s"] / 2
