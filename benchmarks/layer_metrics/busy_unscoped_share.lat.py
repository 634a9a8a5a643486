"""Percent of the first device's op self time, in the traced steps, in ops
whose op_name holds none of the program's scope names. Prints self seconds
by scope as note device_time_by_scope."""
from benchmarks.harness import spans

Read = spans.BusyUnscopedShare
