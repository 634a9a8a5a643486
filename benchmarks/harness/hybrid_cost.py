"""What the two kernels of a stack of scan layers and differential-attention
layers need, and what of the program's counters a reader takes (new with
PR 37; nothing else in the harness reads it).

The stack (PERF.md section 4, `phi4flash`): of `num_layers` = n layers (a
multiple of 4) the even ones up to n / 2 are scan layers (n / 4 + 1), the
odd ones below n / 2 attend within `sliding_window` keys (n / 4), layer
n / 2 + 1 attends over everything, and the odd ones after it attend over
everything through ITS pages (n / 4 layers of whole context in all, all but
one of them reading pages they do not own). The even ones after it keep no
state and run no kernel.

The kernels' names in a trace (`xplane.KernelSeconds`): `diff_attend`,
`ssm_scan`, the `jax.named_scope` directly round each kernel's call.

The engine's step records carry, where the program has them, the cumulative
counters `ssm_tokens` (valid tokens dispatched), `ssm_rows` and
`cross_tokens_unread`; a program without them (the parent of PR 37) gives
records with none, and `moe_cost.CounterDeltas` then returns None.
"""

from __future__ import annotations

import json

from benchmarks.harness import flops
from benchmarks.harness import xplane

DIFF_ATTEND = "diff_attend"
SSM_SCAN = "ssm_scan"


def KernelShare(run, scope: str):
  """The kernels named after `scope` over the first device's busy time in
  the traced steps; None where the trace holds none."""
  kernel_s = xplane.KernelSeconds(run["trace"], scope)
  if kernel_s is None:
    return None
  return 100.0 * kernel_s / run["trace"]["busy_s"]


def TracedStepRows(run, n: int) -> list:
  """The live rows of the `n` steps a trace holds: the last n that were done
  when the window closed. The trace stops there, and the engine runs on
  while the probe waits, so the last n steps the recorder saw are later ones
  with other rows (PERF.md section 7(q))."""
  records, rows = run.get("step_records"), run["step_rows"]
  if not records or "window" not in run:
    return rows[-n:]
  done = sum(1 for rec in records if rec[0] <= run["window"][1])
  return rows[max(0, done - n):done]


def KernelRoofline(run, scope: str, step_cost):
  """The kernels' device time in the traced steps against the larger of
  their HBM and MXU times, `step_cost(rows) -> (operations, bytes)` summed
  over the same steps' live rows; None where the trace holds no such
  kernel. Notes what it counted under `<scope>_roofline`."""
  kernel_s = xplane.KernelSeconds(run["trace"], scope)
  if kernel_s is None:
    return None
  n = run["trace_step"]["count"]
  ops = nbytes = 0.0
  for rows in TracedStepRows(run, n):
    o, b = step_cost(rows)
    ops, nbytes = ops + o, nbytes + b
  share, bound = flops.RooflineShare(ops, nbytes, kernel_s, run["peak"])
  print(json.dumps({"note": scope + "_roofline", "value": {
      "bound": bound, "ops": ops, "bytes": nbytes, "steps": n,
      "kernel_s": kernel_s}}), flush=True)
  return share


def Layers(sizes: dict) -> dict:
  """How many layers of each kind the file's depth holds."""
  n = int(sizes["num_layers"])
  assert n % 4 == 0, n
  return {"scan": n // 4 + 1, "window": n // 4, "whole_context": n // 4}


def Window(sizes: dict) -> int:
  return int(sizes["task_params"]["sliding_window_size"])


def DiffAttendStepCost(rows: list[tuple[int, int]], packed_tokens: int,
                       sizes: dict, bytes_per_elem: int = 2
                       ) -> tuple[float, float]:
  """(operations, bytes) differential attention needs for one step.

  rows: (tokens this step, context length after the step) of each live row.
  A token at position p attends to p + 1 cached tokens on a whole-context
  layer and to min(p + 1, w) on a window layer, with two softmaxes a query
  pair: every one of the N query heads takes a score of H products against
  its K head and weighs a V of 2H, so 2 * N * H + 2 * N * 2H operations an
  attended token. A row's K and V pages are read once a layer, as far back
  as its first token's window reaches (min(context, w + tokens - 1)) or
  whole, Nk heads of H each; q (N heads of H) is read and the output (N / 2
  pairs of 2H) written once for the whole packed axis."""
  n, h = sizes["num_heads"], sizes["dim_per_head"]
  nk = sizes["num_kv_heads"]
  layers, w = Layers(sizes), Window(sizes)
  ops = nbytes = 0.0
  for window, count in ((w, layers["window"]), (0, layers["whole_context"])):
    attended = 0.0
    kv_tokens = 0
    for new, ctx in rows:
      if new <= 0:
        continue
      first = ctx - new + 1           # context seen by the row's first token
      reach = min(ctx, window) if window else ctx
      whole = max(0, reach - first + 1)   # tokens whose context w does not cut
      attended += whole * (first + reach) / 2.0 + (new - whole) * window
      kv_tokens += min(ctx, window + new - 1) if window else ctx
    ops += count * (2.0 * n * h + 2.0 * n * 2 * h) * attended
    nbytes += count * bytes_per_elem * (
        2.0 * kv_tokens * nk * h + packed_tokens * (n * h + n // 2 * 2 * h))
  return ops, nbytes


def SsmScanStepCost(rows: list[tuple[int, int]], sizes: dict
                    ) -> tuple[float, float]:
  """(operations, bytes) the selective scan needs for one step: a live row's
  state [E, N] f32 read and written once a scan layer, each token's step
  size, input and output (E each, f32) and its B and C (N each); about 9
  operations a state element a token (the decay's product and exponential,
  the state's multiply-add, the input's two products, the read-out's
  multiply-add)."""
  tp = sizes["task_params"]
  e = int(tp["mixer_tpl.expand"]) * sizes["model_dim"]
  n = int(tp["mixer_tpl.state_dim"])
  live = sum(1 for new, _ in rows if new > 0)
  tokens = sum(new for new, _ in rows if new > 0)
  layers = Layers(sizes)["scan"]
  ops = layers * 9.0 * e * n * tokens
  nbytes = layers * 4.0 * (2.0 * e * n * live + tokens * (3.0 * e + 2.0 * n))
  return ops, nbytes
