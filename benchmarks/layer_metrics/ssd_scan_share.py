"""The Mamba-2 scan's operations (the program's `ssd_scan` scope) over the
first device's busy time in the traced steps."""
from benchmarks.harness import nemotron_cost


def Read(run):
  return nemotron_cost.ScopeShare(run, nemotron_cost.SSD_SCAN)
