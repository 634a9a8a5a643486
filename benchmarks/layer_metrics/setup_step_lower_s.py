"""Seconds of set-up in which a named step program lowered its jaxpr to a
module: `lower_s` of the programs' compile records, from the start-up
record's events."""
from benchmarks.harness import startup


def Read(run):
  return startup.StepPrograms(run, "step_lower")
