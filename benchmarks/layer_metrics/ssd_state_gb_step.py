"""GB of Mamba-2 slot state read and written a step over the window's steps
(engine counter `ssd_state_rows` times a layer's state a slot, twice): what
a narrower state, or a pass over the rows that changed alone, would move."""
from benchmarks.harness import granite_cost

Read = granite_cost.StateGbStep
