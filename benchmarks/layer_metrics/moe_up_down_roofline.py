"""The two grouped matmuls of an ungated expert layer (up, down) against the
larger of their HBM and MXU times over the traced steps, at the width the
file states (harness/nemotron_cost.py)."""
from benchmarks.harness import nemotron_cost

Read = nemotron_cost.UpDownRoofline
