"""MB of slot state read and written a step over the window's steps (engine
counter `slot_state_bytes`): the convolution tails of the step's live rows,
once in and once out (harness/lfm2_cost.py)."""
from benchmarks.harness import lfm2_cost

Read = lfm2_cost.SlotStateMbStep
