"""Milliseconds a step under `moe_shared`: the shared expert every token
goes through, beside the routed ones."""
from benchmarks.harness import nemotron_cost


def Read(run):
  return nemotron_cost.ScopeMs(run, nemotron_cost.MOE_SHARED)
