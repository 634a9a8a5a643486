"""Median over the window's steps of the host's time between one step's
results arriving and the next step's launch: commit and the second lock wait
of step n, the loop's turn-around, and lock_wait, admit, build, draft, h2d
and dispatch of step n + 1 (the engine's step records)."""
from benchmarks.harness import spans

Read = spans.StepHostMs
