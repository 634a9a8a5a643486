"""Device busy time per train step, from the trace: the union of op
intervals over the whole steps traced, mean over the chips, per step."""


def Read(run):
  steps = run["trace_step"]["count"]
  return 1e3 * run["trace"]["busy_s"] / steps
