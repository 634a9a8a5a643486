"""The selective scan's kernels' device time in the traced steps against the
larger of their HBM and MXU times (harness/hybrid_cost.py counts both from
each live row's tokens over the same steps; the chip's published peak is its
matrix unit's, which a scan cannot use, so the share is in effect one of HBM
time)."""
from benchmarks.harness import hybrid_cost


def Read(run):
  return hybrid_cost.KernelRoofline(
      run, hybrid_cost.SSM_SCAN,
      lambda rows: hybrid_cost.SsmScanStepCost(rows, run["sizes"]))
