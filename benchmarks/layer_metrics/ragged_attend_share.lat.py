"""The ragged attend kernel's share of device busy time in the trace."""
from benchmarks.harness import layer_lib

Read = layer_lib.RaggedShare
