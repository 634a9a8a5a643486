"""Seconds of set-up in which a named step program (`ragged`, `feed`; the
train `step` and `loop`) traced its jaxpr: `trace_s` of the programs' compile
records, from the start-up record's events. The note startup_step_programs
carries each program's row with `cache_hit` and `thread`."""
from benchmarks.harness import startup


def Read(run):
  return startup.StepPrograms(run, "step_trace")
