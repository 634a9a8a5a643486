"""Milliseconds a step under `post_norm`: the norm on a branch's OUTPUT
before the residual add, the attention branch's and the feed-forward's
(dense and experts). None where the program declares no such scope."""
from benchmarks.harness import scope_ms


def Read(run):
  if "post_norm" not in (scope_ms.Registry() or {}):
    return None
  return scope_ms.Rolled(run, "post_norm")
