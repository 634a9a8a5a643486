"""The trace reduction, on the small trace recorded on a TPU v5e from the
dense1b_train_packed cell (two train steps; benchmarks/tools/trace_fixture.py
cut it) and on hand-made events."""

import os

import pytest

from benchmarks.harness import xplane

_TRACE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "data",
    "trace_train_small.json.gz")


@pytest.fixture(scope="module")
def trace():
  return xplane.Load(_TRACE)


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
