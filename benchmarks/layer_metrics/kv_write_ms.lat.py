"""Milliseconds a step under `kv_write`: `kv_layout` (the gathers in front of
the page write) and the write itself, scatter or kernel."""
from benchmarks.harness import scope_ms


def Read(run):
  return scope_ms.Rolled(run, "kv_write")
