"""Milliseconds a step under `short_conv_taps`, inside `short_conv`: the
packed causal depthwise sum, the slot tail's gather and its write-back, what
of the mixer is neither a matmul nor a gate (harness/lfm2_cost.py). None
where the program declares no such scope."""
from benchmarks.harness import lfm2_cost

Read = lfm2_cost.ShortConvTapsMs
