"""The latent attend kernels (`mla_attend`) over the first device's busy time
in the traced steps (harness/mla_cost.py)."""
from benchmarks.harness import mla_cost

Read = mla_cost.AttendShare
