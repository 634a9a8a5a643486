"""The expert layers' grouped matmuls (`moe_cost.KERNEL_SCOPES`) over the
first device's busy time in the traced steps."""
from benchmarks.harness import moe_cost
from benchmarks.harness import xplane


def Read(run):
  kernel_s = xplane.KernelSeconds(run["trace"], *moe_cost.KERNEL_SCOPES)
  if kernel_s is None:
    return None
  return 100.0 * kernel_s / run["trace"]["busy_s"]
