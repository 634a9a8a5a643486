"""Median duration of the engine's own `lingvo/serve/step` span over the
window's step records (observe.trace.StepTrace, taken inside
ServingLoop.StepOnce): the inside twin of engine_step_ms."""
from benchmarks.harness import spans

Read = spans.StepSpanMs
