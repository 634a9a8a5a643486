"""What the kernels of a stack of power-retention layers need, and what of
the program's scopes a reader takes (new with PR 47; nothing else in the
harness reads it).

The stack (PERF.md section 4, `brumby14b`): `num_layers` layers, each a
power-retention mixer (degree 2) and a dense feed-forward. N query heads
over Nkv KV heads of H; the state of a (row, layer) is S `[Nkv, D, H]` and z
`[Nkv, D]` in f32 with D = H (H + 1) / 2 = 8,256 at H = 128, the distinct
products of a head's dimensions: the costs count D whatever the program
stores (it stores 8,320 = 65 x 128, what its kernels build by lane
rotations).

Each cost is the same work whatever implements it:

- the state (`retention_state`): a live row's S and z read once a layer a
  step; read and written once more for every page folded into them; 2 D (H +
  1) operations a (query head, token) for the query, and as many a (KV head,
  folded token) for the fold;
- the open chunk (`retention_chunk`): the K and V (16 bits) and the gates
  (f32) of the tokens a row's step attends in pages, read once a layer, each
  query token's q read and output written once (N heads of H, 16 bits in, f32
  out); 2 x 2 x H operations a (query head, attended token) pair.

Device time is read by the program's own scopes (`scope_ms`: every device op
of the traced steps under the innermost declared scope of its `op_name`): the
kernels are named after `retention_state` and `retention_chunk`, and the
gathers and descriptors round them lie under the same scopes. A program that
declares no such scope (the parent of PR 47) gives every function here None.
"""

from __future__ import annotations

import json

from benchmarks.harness import flops
from benchmarks.harness import hybrid_cost
from benchmarks.harness import nemotron_cost

STATE = "retention_state"
CHUNK = "retention_chunk"
MIXER = ("qk_norm", "retention_gate", "retention_out")

ScopeMs = nemotron_cost.ScopeMs
ScopeShare = nemotron_cost.ScopeShare


def FeatureDim(h: int) -> int:
  """Distinct products a_i a_j, i <= j, of a head's h dimensions."""
  return h * (h + 1) // 2


def StateBytes(sizes: dict) -> int:
  """S and z of one (row, layer), f32, at the dimension the costs count."""
  h = sizes["dim_per_head"]
  return 4 * sizes["num_kv_heads"] * FeatureDim(h) * (h + 1)


def _Page(sizes: dict) -> int:
  return int(sizes["serving"]["page_size"])


def StateStepCost(rows: list[tuple[int, int]], sizes: dict
                  ) -> tuple[float, float]:
  """(operations, bytes) the state's query and fold need for one step.
  rows: (tokens this step, context length after the step) of each live
  row."""
  n, nk, h = sizes["num_heads"], sizes["num_kv_heads"], sizes["dim_per_head"]
  page, d = _Page(sizes), FeatureDim(h)
  layers = int(sizes["num_layers"])
  state = StateBytes(sizes)
  ops = nbytes = 0.0
  for new, ctx in rows:
    if new <= 0:
      continue
    before = ctx - new
    folded = ctx // page - before // page        # pages the step completes
    ops += 2.0 * d * (h + 1) * (n * new + nk * folded * page)
    nbytes += state * (1.0 + 2.0 * folded)
  return layers * ops, layers * nbytes


def ChunkStepCost(rows: list[tuple[int, int]], sizes: dict
                  ) -> tuple[float, float]:
  """(operations, bytes) the attention form over the open chunk and the
  step's own tokens needs for one step."""
  n, nk, h = sizes["num_heads"], sizes["num_kv_heads"], sizes["dim_per_head"]
  page = _Page(sizes)
  layers = int(sizes["num_layers"])
  ops = nbytes = 0.0
  for new, ctx in rows:
    if new <= 0:
      continue
    held = (ctx - new) % page                    # the open chunk at the start
    attended = new * held + new * (new + 1) / 2.0
    ops += 2.0 * 2 * h * n * attended
    nbytes += (held + new) * nk * (2 * 2 * h + 4) + new * n * h * (2 + 4)
  return layers * ops, layers * nbytes


def _Roofline(run, scope: str, step_cost):
  seconds = nemotron_cost.ScopeSeconds(run, scope)
  if seconds is None:
    return None
  n = run["trace_step"]["count"]
  ops = nbytes = 0.0
  for rows in hybrid_cost.TracedStepRows(run, n):
    o, b = step_cost(rows, run["sizes"])
    ops, nbytes = ops + o, nbytes + b
  share, bound = flops.RooflineShare(ops, nbytes, seconds, run["peak"])
  print(json.dumps({"note": scope + "_roofline", "value": {
      "bound": bound, "ops": ops, "bytes": nbytes, "steps": n,
      "scope_s": seconds}}), flush=True)
  return share


def StateRoofline(run):
  """The `retention_state` scope's device time in the traced steps against
  the larger of its HBM and MXU times, from the same steps' live rows."""
  return _Roofline(run, STATE, StateStepCost)


def ChunkRoofline(run):
  """The same for `retention_chunk`."""
  return _Roofline(run, CHUNK, ChunkStepCost)
