"""Milliseconds a step under `short_conv`: a gated short-convolution mixer's
whole branch (the projection to B, C and X, the two gates, the taps and the
slot tail, the output projection), summed over the stack's such layers
(harness/lfm2_cost.py). None where the program declares no such scope."""
from benchmarks.harness import lfm2_cost

Read = lfm2_cost.ShortConvMs
