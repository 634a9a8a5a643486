"""The differential attend kernels (`hybrid_cost.DIFF_ATTEND`) over the
first device's busy time in the traced steps."""
from benchmarks.harness import hybrid_cost


def Read(run):
  return hybrid_cost.KernelShare(run, hybrid_cost.DIFF_ATTEND)
