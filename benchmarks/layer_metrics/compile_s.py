"""Seconds JAX spent tracing, lowering and compiling (or fetching from the
cache) in the whole run, from jax.monitoring durations."""


def Read(run):
  return run["compile_s"]
