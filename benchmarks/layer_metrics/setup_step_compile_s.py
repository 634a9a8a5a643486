"""Seconds of set-up in which the backend compiled a named step program or
the compile cache fetched it: `backend_s + fetch_s` of the programs' compile
records, from the start-up record's events (`cache_hit` in the note
startup_step_programs says which)."""
from benchmarks.harness import startup


def Read(run):
  return startup.StepPrograms(run, "step_compile")
