"""The retention state's query and fold (the program's `retention_state`
scope: its kernels and the operands gathered for them) over the first
device's busy time in the traced steps."""
from benchmarks.harness import retention_cost


def Read(run):
  return retention_cost.ScopeShare(run, retention_cost.STATE)
