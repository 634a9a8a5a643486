"""Seconds of StepTrace.compile_s on the step records that close inside
run["window"]: what compiled on the engine's thread inside the measured
window. 0.0 expected; the note window_compile names each such step with its
programs."""
from benchmarks.harness import startup

Read = startup.WindowCompile
