"""Milliseconds a step of the first device under the program's `ffn` scope,
its children (`moe_*`) included (scope_ms: note device_ms_by_scope)."""
from benchmarks.harness import scope_ms


def Read(run):
  return scope_ms.Rolled(run, "ffn")
