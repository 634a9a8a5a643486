"""The retention state's device time in the traced steps against the larger
of its HBM and MXU times (harness/retention_cost.py counts both from each
live row over the same steps: S and z of 34 MB a (row, layer) read once a
step and read and written once more a folded page, so in a decode step the
share is in effect one of HBM time)."""
from benchmarks.harness import retention_cost

Read = retention_cost.StateRoofline
