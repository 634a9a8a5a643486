"""p95 of time to first token from the due time, client clock; a request
with no token by the end of the window counts for its wait so far."""
from benchmarks.harness import layer_lib


def Read(run):
  return layer_lib.Pct(run, "ttft_ms", 95)
