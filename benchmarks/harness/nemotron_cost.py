"""What the kernels of a stack of single-branch layers (Mamba-2 mixers,
two-matrix experts, one grouped-query attention layer among them) need, and
what of the program's counters and scopes a reader takes (new with PR 45;
nothing else in the harness reads it).

The stack (PERF.md section 4, `nemotron3nano`): the first `num_layers`
letters of the file's `hybrid_override_pattern`, `M` a Mamba-2 mixer, `E` an
expert feed-forward, `*` attention; a layer is one of them and nothing else.

Each cost is the same work whatever implements it: the scan is counted a
token at a time (its recurrence), whether the program runs it so or in
chunks; an expert's matrices at the width the file states (1856), whether
the program stores them padded or not.

Device time is read by the program's own scopes (`scope_ms`: every device op
of the traced steps under the innermost declared scope of its `op_name`): the
scan is a kernel and XLA operations round it, all under `ssd_scan`, and the
grouped matmuls are megablox's kernels or `ragged_dot`'s operations under
`moe_experts`, by the width. The attention layer's kernel is the grouped
ragged attend, named after `ragged_attend`. A program that declares no such
scope (the parent of PR 45) gives every function here None.
"""

from __future__ import annotations

import json

from benchmarks.harness import flops
from benchmarks.harness import hybrid_cost
from benchmarks.harness import layer_lib
from benchmarks.harness import moe_cost
from benchmarks.harness import scope_ms
from benchmarks.harness import xplane

SSD_SCAN = "ssd_scan"
MOE_EXPERTS = "moe_experts"
MOE_SHARED = "moe_shared"
SSD_MIXER = ("ssd_in_proj", "ssd_conv", "ssd_gate_norm", "ssd_out_proj")


def Layers(sizes: dict) -> dict:
  """{letter: layers of it} over the depth the file runs."""
  pattern = sizes["task_params"]["hybrid_override_pattern"][
      :int(sizes["num_layers"])]
  return {letter: pattern.count(letter) for letter in "ME*"}


def ScopeMs(run, *names):
  """Milliseconds a step under the named scopes, children included; None
  where the program declares none of them."""
  declared = scope_ms.Registry()
  if declared is None or not any(n in declared for n in names):
    return None
  return scope_ms.Rolled(run, *names)


def ScopeSeconds(run, *names):
  """The traced steps' device seconds under the named scopes."""
  ms = ScopeMs(run, *names)
  if not ms:
    return None
  return ms * 1e-3 * run["trace_step"]["count"]


def ScopeShare(run, *names):
  """Those scopes over the first device's busy time in the traced steps."""
  ms = ScopeMs(run, *names)
  tree = scope_ms.ByScope(run)
  if ms is None or not tree or not tree.get("busy_ms"):
    return None
  return 100.0 * ms / tree["busy_ms"]


def SsdScanStepCost(rows: list[tuple[int, int]], sizes: dict
                    ) -> tuple[float, float]:
  """(operations, bytes) the Mamba-2 scan needs for one step: a live row's
  state [Hm, P, N] f32 read and written once a Mamba-2 layer; each token's
  input and output (E each, f32), its step size (Hm) and its B and C (G N
  each); 5 operations a state element a token (the decay's product, the
  rank-one update's multiply-add, the read-out's multiply-add)."""
  tp = sizes["task_params"]
  hm, p = int(tp["mixer_tpl.num_heads"]), int(tp["mixer_tpl.head_dim"])
  g, n = int(tp["mixer_tpl.num_groups"]), int(tp["mixer_tpl.state_dim"])
  e = hm * p
  live = sum(1 for new, _ in rows if new > 0)
  tokens = sum(new for new, _ in rows if new > 0)
  layers = Layers(sizes)["M"]
  ops = layers * 5.0 * e * n * tokens
  nbytes = layers * 4.0 * (2.0 * e * n * live
                           + tokens * (2.0 * e + hm + 2.0 * g * n))
  return ops, nbytes


def SsdScanRoofline(run):
  """The scan's device time in the traced steps against the larger of its
  HBM and MXU times, from the same steps' live rows."""
  seconds = ScopeSeconds(run, SSD_SCAN)
  if seconds is None:
    return None
  n = run["trace_step"]["count"]
  ops = nbytes = 0.0
  for rows in hybrid_cost.TracedStepRows(run, n):
    o, b = SsdScanStepCost(rows, run["sizes"])
    ops, nbytes = ops + o, nbytes + b
  share, bound = flops.RooflineShare(ops, nbytes, seconds, run["peak"])
  print(json.dumps({"note": "ssd_scan_roofline", "value": {
      "bound": bound, "ops": ops, "bytes": nbytes, "steps": n,
      "scope_s": seconds}}), flush=True)
  return share


def UpDownCost(pairs: float, active_experts: float, model_dim: int,
               expert_dim: int, bytes_per_elem: int = 2
               ) -> tuple[float, float]:
  """(operations, bytes) of the up and down projections of `pairs` (token,
  expert) pairs over `active_experts` experts that got a token (both summed
  over layers and steps): 2 x 2 x D x F operations a pair; the two [D, F]
  matrices of every active expert read once, each pair's D-vector read once
  and written once (the F-wide hidden vector need not leave the chip)."""
  ops = 2.0 * 2 * model_dim * expert_dim * pairs
  nbytes = bytes_per_elem * (2.0 * model_dim * expert_dim * active_experts
                             + 2.0 * model_dim * pairs)
  return ops, nbytes


def UpDownRoofline(run):
  """The two grouped matmuls' device time in the traced steps against the
  larger of their HBM and MXU times, at the width the file states, from the
  step records' routed pairs and active experts over the same steps."""
  seconds = ScopeSeconds(run, MOE_EXPERTS)
  n = run["trace_step"]["count"]
  grew = moe_cost.CounterDeltas(
      run, ("moe_tokens_routed", "moe_experts_active"), last_steps=n)
  if seconds is None or grew is None:
    return None
  s = run["sizes"]
  ops, nbytes = UpDownCost(
      grew["moe_tokens_routed"], grew["moe_experts_active"], s["model_dim"],
      moe_cost.ExpertWidth(s))
  share, bound = flops.RooflineShare(ops, nbytes, seconds, run["peak"])
  print(json.dumps({"note": "moe_up_down_roofline", "value": {
      "bound": bound, "ops": ops, "bytes": nbytes, "steps": n,
      "scope_s": seconds, "pairs": grew["moe_tokens_routed"],
      "active_experts": grew["moe_experts_active"]}}), flush=True)
  return share


def GqaAttendRoofline(run):
  """The grouped ragged attend kernel of the stack's attention layers (one
  of nine here) against its roofline over the traced steps: every query
  head's products, the KV heads' pages."""
  kernel_s = xplane.KernelSeconds(run["trace"], layer_lib.RAGGED_KERNEL)
  if kernel_s is None:
    return None
  s = run["sizes"]
  n = run["trace_step"]["count"]
  ops = nbytes = 0.0
  for rows in hybrid_cost.TracedStepRows(run, n):
    o, b = flops.RaggedAttendStepCost(
        rows, run["packed_t"], s["num_heads"], s["dim_per_head"],
        Layers(s)["*"], num_kv_heads=s["num_kv_heads"])
    ops, nbytes = ops + o, nbytes + b
  share, bound = flops.RooflineShare(ops, nbytes, kernel_s, run["peak"])
  print(json.dumps({"note": "gqa_attend_roofline", "value": {
      "bound": bound, "ops": ops, "bytes": nbytes, "steps": n,
      "kernel_s": kernel_s}}), flush=True)
  return share
