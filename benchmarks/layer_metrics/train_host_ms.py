"""Median over the window's loops of TrainProgram's host_overhead_s minus
infeed_wait_s: placing and dispatching one loop on the main thread."""
from benchmarks.harness import spans

Read = spans.TrainHostMs
