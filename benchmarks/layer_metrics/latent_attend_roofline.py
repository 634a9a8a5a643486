"""The latent attend kernel against its roofline over the traced steps: 2 x
heads x (320 + 256) operations an attended token a layer, a latent row once
a (query block, context token) a layer (harness/mla_cost.py)."""
from benchmarks.harness import mla_cost

Read = mla_cost.AttendRoofline
