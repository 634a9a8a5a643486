"""The differential attend kernels' device time in the traced steps against
the larger of their HBM and MXU times (harness/hybrid_cost.py counts both
from each live row's tokens and context over the same steps)."""
from benchmarks.harness import hybrid_cost


def Read(run):
  return hybrid_cost.KernelRoofline(
      run, hybrid_cost.DIFF_ATTEND,
      lambda rows: hybrid_cost.DiffAttendStepCost(rows, run["packed_t"],
                                                  run["sizes"]))
