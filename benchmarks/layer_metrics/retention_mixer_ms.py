"""Milliseconds a step under a power-retention layer's scopes other than its
kernels' (`qk_norm`, `retention_gate`, `retention_out`): what the layer does
round its kernels and its projections."""
from benchmarks.harness import retention_cost


def Read(run):
  return retention_cost.ScopeMs(run, *retention_cost.MIXER)
