"""The grouped ragged attend kernel against its roofline over the traced
steps, counted for the layers that attend (harness/nemotron_cost.py)."""
from benchmarks.harness import nemotron_cost

Read = nemotron_cost.GqaAttendRoofline
