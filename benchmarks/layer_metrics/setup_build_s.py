"""Seconds of set-up under the program's phase `build` (ServingLoop.__init__
whole; a train program's and the executor's construction), less the compile
events inside it: observe.profile's start-up record, tiled by
benchmarks/harness/startup.py."""
from benchmarks.harness import startup


def Read(run):
  return startup.Part(run, "build")
