"""What several per-layer readers share (the .lat and .tput forms of one
quantity). A reader is benchmarks/layer_metrics/<metric>.py with Read(run);
`run` holds the run's counters, client-side samples and, in a traced run, the
trace reduction (`trace`) and the step program's executions (`trace_step`)."""

from __future__ import annotations

import json
import statistics

from benchmarks.harness import flops
from benchmarks.harness import readings


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
  return 100.0 * run["trace"]["kernel_s"] / run["trace"]["busy_s"]


def RaggedRoofline(run):
  """Required operations and bytes of the traced steps (from each live row's
  tokens and context) against the kernel's device time in the same steps."""
  n = run["trace_step"]["count"]
  s = run["sizes"]
  ops = nbytes = 0.0
  for rows in run["step_rows"][-n:]:
    o, b = flops.RaggedAttendStepCost(
        rows, run["packed_t"], s["num_heads"], s["dim_per_head"],
        s["num_layers"])
    ops, nbytes = ops + o, nbytes + b
  share, bound = flops.RooflineShare(ops, nbytes, run["trace"]["kernel_s"],
                                     run["peak"])
  print(json.dumps({"note": "ragged_attend_roofline", "value": {
      "bound": bound, "ops": ops, "bytes": nbytes, "steps": n}}), flush=True)
  return share


def Pct(run, key, q):
  """None where the run took no such sample (nothing to read)."""
  return readings.Percentile(run[key], q) if run[key] else None
