"""What several per-layer readers share (the .lat and .tput forms of one
quantity). A reader is benchmarks/layer_metrics/<metric>.py with Read(run);
`run` holds the run's counters, client-side samples and, in a traced run, the
trace reduction (`trace`) and the step program's executions (`trace_step`)."""

from __future__ import annotations

import statistics

from benchmarks.harness import flops
from benchmarks.harness import hybrid_cost
from benchmarks.harness import readings
from benchmarks.harness import xplane

RAGGED_KERNEL = "ragged_attend"   # the scope the ragged attend kernel's op is
#                                   named after (xplane.ScopeOfOp)


def EngineStepMs(run):
  return statistics.median(run["step_durations_ms"])


def PackedOccupancy(run):
  """Valid over packed tokens: tokens whose KV entered the cache or that were
  streamed, between the first and the last step completion in the window,
  over the packed width of the steps between them."""
  t0, t1 = run["window"]
  inside = [r for r in run["step_records"] if t0 <= r[0] <= t1]
  steps = inside[-1][2] - inside[0][2]
  return 100.0 * (inside[-1][3] - inside[0][3]) / (steps * run["packed_t"])


def AttendBlockFill(run):
  """Percent of the ragged kernel's query rows that held a valid query:
  queries over (blocks x Bq), between the first and the last step completion
  in the window. A decode row fills one row of its block (1 / Bq), a prefill
  chunk fills whole blocks. None where the program counts no blocks."""
  t0, t1 = run["window"]
  inside = [a for r, a in zip(run["step_records"], run["attend_blocks"])
            if t0 <= r[0] <= t1]
  blocks = inside[-1][0] - inside[0][0]
  if not run["attend_bq"] or blocks <= 0:
    return None
  return 100.0 * (inside[-1][1] - inside[0][1]) / (blocks * run["attend_bq"])


def KvPoolPeak(run):
  kv = run["kv_pages"]
  return 100.0 * kv["peak_in_use"] / kv["num_pages"]


def RaggedShare(run):
  """The ragged attend kernel's own time (not another kernel's beside it)
  over the device's busy time. None where the trace holds no such kernel."""
  kernel_s = xplane.KernelSeconds(run["trace"], RAGGED_KERNEL)
  if kernel_s is None:
    return None
  return 100.0 * kernel_s / run["trace"]["busy_s"]


def RaggedRoofline(run):
  """Required operations and bytes of the traced steps (each live row's
  tokens and context in the steps the trace holds: `hybrid_cost.
  TracedStepRows`, not the last steps the recorder saw, which lie after the
  trace) against the kernel's device time in the same steps.
  K and V bytes by the configuration's `num_kv_heads` and each layer's
  context by its `attention_windows` (one entry a layer, 0 = full), where
  the file states them; absent, every query head has its own K and V and
  every layer is full. None where the trace holds no such kernel."""
  s = run["sizes"]
  return hybrid_cost.KernelRoofline(
      run, RAGGED_KERNEL, lambda rows: flops.RaggedAttendStepCost(
          rows, run["packed_t"], s["num_heads"], s["dim_per_head"],
          s["num_layers"], num_kv_heads=s.get("num_kv_heads"),
          windows=s.get("attention_windows")))


def Pct(run, key, q):
  """None where the run took no such sample (nothing to read)."""
  return readings.Percentile(run[key], q) if run[key] else None
