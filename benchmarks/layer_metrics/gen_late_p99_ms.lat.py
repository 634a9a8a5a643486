"""How late the client sent: p99 of sent - due, client clock."""
from benchmarks.harness import layer_lib


def Read(run):
  return layer_lib.Pct(run, "gen_late_ms", 99)
