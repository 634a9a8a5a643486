"""What `benchmarks/harness/hybrid_cost.py` counts for the two kernels of a
stack of scan layers and differential-attention layers, on hand-made rows,
and what its readers do with a run that has nothing of theirs."""

import json
import types

import pytest

from benchmarks.harness import flops
from benchmarks.harness import hybrid_cost
from benchmarks.harness import peaks
from benchmarks.harness import spec

_SIZES = dict(
    num_layers=32, model_dim=2560, num_heads=40, num_kv_heads=20,
    dim_per_head=64,
    task_params={"sliding_window_size": 512, "mixer_tpl.expand": 2,
                 "mixer_tpl.state_dim": 16})
_OPS_A_TOKEN = 2 * 40 * 64 + 2 * 40 * 128
_KV_A_TOKEN = 2 * 2 * 20 * 64                 # K and V, bf16
_QO_A_TOKEN = 2 * (40 * 64 + 20 * 128)


def _Read(name, run):
  return spec.LayerMetricReader(name)(spec.RunData(run))


@pytest.mark.parametrize("depth,want", [
    (8, {"scan": 3, "window": 2, "whole_context": 2}),
    (32, {"scan": 9, "window": 8, "whole_context": 8})])
def test_layers_of_each_kind_by_depth(depth, want):
  assert hybrid_cost.Layers({"num_layers": depth}) == want


@pytest.mark.parametrize("rows,attended_window,attended_whole,kv_window,kv_whole", [
    # a decode row inside the window: both kinds of layer see all of it
    ([(1, 300)], 300, 300, 300, 300),
    # a decode row past it: the window layers stop at 512 keys
    ([(1, 2000)], 512, 2000, 512, 2000),
    # a prompt's first chunk: token p sees p + 1 keys
    ([(4, 4)], 1 + 2 + 3 + 4, 1 + 2 + 3 + 4, 4, 4),
    # a chunk that crosses the window: 511, 512, then 512 again
    ([(3, 513)], 511 + 512 + 512, 511 + 512 + 513, 513, 513),
    # a chunk deep in a long prompt reads its window and its own tokens back
    ([(512, 4096)], 512 * 512, sum(range(3585, 4097)), 512 + 511, 4096),
    # rows add up, and a row with no token this step costs nothing
    ([(1, 300), (0, 77), (1, 2000)], 812, 2300, 812, 2300),
])
def test_diff_attend_cost(rows, attended_window, attended_whole, kv_window,
                          kv_whole):
  ops, nbytes = hybrid_cost.DiffAttendStepCost(rows, 576, _SIZES)
  assert ops == pytest.approx(
      8 * _OPS_A_TOKEN * (attended_window + attended_whole))
  assert nbytes == pytest.approx(
      8 * _KV_A_TOKEN * (kv_window + kv_whole) + 16 * 576 * _QO_A_TOKEN)


@pytest.mark.parametrize("rows,live,tokens", [
    ([(1, 300)] * 64, 64, 64),
    ([(1, 300)] * 10 + [(512, 1024), (0, 5)], 11, 522)])
def test_ssm_scan_cost(rows, live, tokens):
  ops, nbytes = hybrid_cost.SsmScanStepCost(rows, _SIZES)
  assert ops == 9 * 9.0 * 5120 * 16 * tokens
  assert nbytes == 9 * 4.0 * (2 * 5120 * 16 * live
                              + tokens * (3 * 5120 + 2 * 16))


def _Run(kernels, n_traced=3, steps_after_close=4):
  """A run whose trace holds `n_traced` steps before the window closed at
  t = 10 and whose recorder saw `steps_after_close` more, with fewer rows."""
  in_window = [[(1, 500 + i)] * 64 for i in range(6)]
  after = [[(1, 900)] * 5 for _ in range(steps_after_close)]
  rows = in_window + after
  times = [5.0 + i for i in range(6)] + [10.5 + i for i in range(len(after))]
  return {
      "trace": {"kernel_s_by_scope": kernels, "busy_s": 2.0},
      "trace_step": {"count": n_traced}, "window": (0.0, 10.0),
      "step_records": [(t, 0.05, i, 0, 0) for i, t in enumerate(times)],
      "step_rows": rows, "packed_t": 576, "sizes": _SIZES,
      "peak": peaks.PeakOf("TPU v5 lite")}


def test_traced_rows_are_the_windows_last_and_not_the_recorders_last():
  run = _Run({})
  assert hybrid_cost.TracedStepRows(run, 3) == run["step_rows"][3:6]
  assert hybrid_cost.TracedStepRows(run, 60) == run["step_rows"][:6]
  # a run that carries no step times: the recorder's last, as layer_lib reads
  bare = {k: v for k, v in run.items() if k != "step_records"}
  assert hybrid_cost.TracedStepRows(bare, 3) == run["step_rows"][-3:]


@pytest.mark.parametrize("name,scope,cost", [
    ("diff_attend_roofline", "diff_attend",
     lambda rows: hybrid_cost.DiffAttendStepCost(rows, 576, _SIZES)),
    ("ssm_scan_roofline", "ssm_scan",
     lambda rows: hybrid_cost.SsmScanStepCost(rows, _SIZES))])
def test_roofline_readers_count_the_traced_steps(name, scope, cost, capsys):
  run = _Run({scope: 0.5, "kv_write": 0.1})
  ops = nbytes = 0.0
  for rows in run["step_rows"][3:6]:
    o, b = cost(rows)
    ops, nbytes = ops + o, nbytes + b
  want, bound = flops.RooflineShare(ops, nbytes, 0.5, run["peak"])
  assert _Read(name, run) == want
  assert 0 < want < 100
  note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
  assert note == {"note": name, "value": {
      "bound": bound, "ops": ops, "bytes": nbytes, "steps": 3,
      "kernel_s": 0.5}}


@pytest.mark.parametrize("name,scope", [
    ("diff_attend_share", "diff_attend"), ("ssm_scan_share", "ssm_scan")])
def test_share_readers_read_their_own_kernel(name, scope):
  assert _Read(name, _Run({scope: 0.5, "ragged_attend": 1.0})) == 25.0


@pytest.mark.parametrize("name", [
    "diff_attend_share", "diff_attend_roofline", "ssm_scan_share",
    "ssm_scan_roofline", "cross_decoder_unread_share"])
def test_a_program_without_the_kernels_or_counters_gives_nothing(name):
  """The parent of PR 37 under these files: no such kernel in its trace, no
  such counter in its step records; a reader returns None and does not
  raise, and the line leaves the metric out."""
  assert _Read(name, _Run({"ragged_attend": 1.0})) is None


def test_unread_share_is_the_counters_growth_over_the_window(monkeypatch):
  from benchmarks.harness import spans
  records = [types.SimpleNamespace(counters={
      "cross_tokens_unread": 100 + 40 * i, "ssm_tokens": 1000 + 100 * i})
             for i in range(5)]
  monkeypatch.setattr(spans, "StepRecords", lambda run: records)
  assert _Read("cross_decoder_unread_share", _Run({})) == 40.0
  records[:] = [types.SimpleNamespace(counters={"steps": i})
                for i in range(5)]
  assert _Read("cross_decoder_unread_share", _Run({})) is None
