"""The grouped matmuls' device time in the traced steps against the larger of
their HBM and MXU times (harness/moe_cost.py counts both from the step
records' routed pairs and active experts over the same steps)."""
import json

from benchmarks.harness import flops
from benchmarks.harness import moe_cost
from benchmarks.harness import xplane


def Read(run):
  kernel_s = xplane.KernelSeconds(run["trace"], *moe_cost.KERNEL_SCOPES)
  n = run["trace_step"]["count"]
  grew = moe_cost.CounterDeltas(
      run, ("moe_tokens_routed", "moe_experts_active"), last_steps=n)
  if kernel_s is None or grew is None:
    return None
  s = run["sizes"]
  ops, nbytes = moe_cost.GroupedMatmulCost(
      grew["moe_tokens_routed"], grew["moe_experts_active"], s["model_dim"],
      moe_cost.ExpertWidth(s))
  share, bound = flops.RooflineShare(ops, nbytes, kernel_s, run["peak"])
  print(json.dumps({"note": "moe_experts_roofline", "value": {
      "bound": bound, "ops": ops, "bytes": nbytes, "steps": n,
      "pairs": grew["moe_tokens_routed"],
      "active_experts": grew["moe_experts_active"]}}), flush=True)
  return share
