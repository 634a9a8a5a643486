"""The grouped ragged attend kernel at head size 64 (two KV heads a 128-lane
row of the pool) against its roofline over the traced steps, its operations
and bytes counted at the head size the file states for the layers that
attend (harness/lfm2_cost.py)."""
from benchmarks.harness import lfm2_cost

Read = lfm2_cost.H64AttendRoofline
