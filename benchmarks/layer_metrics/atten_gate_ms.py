"""Milliseconds a step under `qk_norm` and `atten_gate`: the RMSNorm over
each head of q and of k before the rotation, and the output gate's own
projection, sigmoid and product before the output projection. None where the
program declares no `atten_gate` scope (a program from before the gate)."""
from benchmarks.harness import scope_ms


def Read(run):
  if "atten_gate" not in (scope_ms.Registry() or {}):
    return None
  return scope_ms.Rolled(run, "qk_norm", "atten_gate")
