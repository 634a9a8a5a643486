"""The first device's idle time in the traced steps, apportioned to the
program's `lingvo/` spans by overlap: the percent under no span of any
thread. Prints idle seconds by span as note idle_by_span."""
from benchmarks.harness import spans

Read = spans.IdleUnspannedShare
