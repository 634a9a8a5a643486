"""Percent of the window's step periods (the engine's step records) that lies
in periods over twice the median one: the seconds of the window in which the
engine was held up, whoever held it. The run's note step_stalls lists them."""
from benchmarks.harness import spans

Read = spans.StepStallShare
