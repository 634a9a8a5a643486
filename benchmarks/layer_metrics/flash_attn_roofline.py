"""Flash attention's kernel events in the trace (every tpu_custom_call of
the train step is one) against the operations and bytes a step requires, per
chip. Prints which bound."""
import json

from benchmarks.harness import flops


def Read(run):
  s = run["sizes"]
  ops, nbytes = flops.FlashTrainStepCost(
      s["batch_size"], s["seq_len"], s["num_heads"], s["dim_per_head"],
      run["layers"])
  steps = run["trace_step"]["count"]
  share, bound = flops.RooflineShare(
      ops * steps / run["chips"], nbytes * steps / run["chips"],
      run["trace"]["kernel_s"], run["peak"])
  print(json.dumps({"note": "flash_attn_roofline", "value": {
      "bound": bound, "kernel_s_per_step": run["trace"]["kernel_s"] / steps}}),
        flush=True)
  return share
