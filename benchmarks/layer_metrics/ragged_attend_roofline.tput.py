"""The ragged attend kernel against its roofline over the traced steps."""
from benchmarks.harness import layer_lib

Read = layer_lib.RaggedRoofline
