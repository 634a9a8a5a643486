"""Median `h2d` phase of the window's engine steps (the engine's step
records): the host placing the step's arguments on the device."""
from benchmarks.harness import spans

Read = spans.StepH2dMs
