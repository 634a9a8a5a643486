"""Seconds of set-up under the program's phase `first_steps` (serve: from
Start()'s return to the commit of the first step that emitted a token; train:
from the first Run's start, or Compile()'s return, to the first loop's
completion), less the compile events inside it."""
from benchmarks.harness import startup


def Read(run):
  return startup.Part(run, "first_steps")
