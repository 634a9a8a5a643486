"""Median wall time of ServingLoop.StepOnce in the window (spans from the
benchmark's side of the call)."""
from benchmarks.harness import layer_lib

Read = layer_lib.EngineStepMs
