"""(p95 - p50) / p50 of the window's loop intervals: the scatter of the loop
readings."""
from benchmarks.harness import readings


def Read(run):
  return readings.LoopJitter(run["intervals"])
