"""Milliseconds a step under `moe_route`, `moe_dispatch` and `moe_combine`:
what the expert layer does round its grouped matmuls."""
from benchmarks.harness import scope_ms


def Read(run):
  return scope_ms.Rolled(run, "moe_route", "moe_dispatch", "moe_combine")
