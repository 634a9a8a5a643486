"""PageAllocator's peak pages in use over the pool."""
from benchmarks.harness import layer_lib

Read = layer_lib.KvPoolPeak
