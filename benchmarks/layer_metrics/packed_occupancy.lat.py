"""Valid over packed tokens of the window's steps, from the engine's token
counters."""
from benchmarks.harness import layer_lib

Read = layer_lib.PackedOccupancy
