"""The Mamba-2 scan's device time in the traced steps against the larger of
its HBM and MXU times (harness/nemotron_cost.py counts both from each live
row's tokens over the same steps; a decode row is one read and one write of
a 2.1 MB state, so the share is in effect one of HBM time)."""
from benchmarks.harness import nemotron_cost

Read = nemotron_cost.SsdScanRoofline
