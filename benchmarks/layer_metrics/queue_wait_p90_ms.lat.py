"""p90 of admit_time - submit_time of the sampled requests (StreamHandle
times, the same the engine feeds serving/queue_wait_s from)."""
from benchmarks.harness import layer_lib


def Read(run):
  return layer_lib.Pct(run, "queue_wait_ms", 90)
