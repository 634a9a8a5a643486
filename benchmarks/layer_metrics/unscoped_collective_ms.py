"""Milliseconds a step in collectives that no layer's block carries: under no
scope of the program's or under `layer_scan` alone (the ones the compiler
inserted: FSDP's gathers of the stacks a scan slices). 0.0 on one chip."""
from benchmarks.harness import scope_ms

Read = scope_ms.UnscopedCollectiveMs
