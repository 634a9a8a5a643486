"""Milliseconds a step under `atten` less the page write, the attend and scan
kernels' scopes and every Pallas kernel: projections, rotary, convolution."""
from benchmarks.harness import scope_ms

Read = scope_ms.AttenDenseMs
