"""TrainProgram's infeed_wait_s summed over the window's loops, over their
wall time."""


def Read(run):
  waits = [r["infeed_wait_s"] for r in run["loop_results"]]
  return 100.0 * sum(waits) / sum(run["intervals"][-len(waits):])
