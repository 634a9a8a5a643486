"""Milliseconds a step under `optimizer_update` (Learner.Apply)."""
from benchmarks.harness import scope_ms


def Read(run):
  return scope_ms.Rolled(run, "optimizer_update")
