"""The kernel that passes over the slots' Mamba-2 states (`ssd_row_pass`:
a live row's 4.2 MB state of ONE group read and written once a layer)
against the larger of its HBM and MXU times in the traced steps
(harness/granite_cost.py counts both from the program's `ssd_state_rows`
and the same steps' live rows): in effect a share of HBM time."""
from benchmarks.harness import granite_cost

Read = granite_cost.RowPassRoofline
