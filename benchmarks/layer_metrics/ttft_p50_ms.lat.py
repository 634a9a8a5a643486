"""Median time to first token from the due time, client clock."""
from benchmarks.harness import layer_lib


def Read(run):
  return layer_lib.Pct(run, "ttft_ms", 50)
