"""Milliseconds a step under a Mamba-2 layer's scopes other than the scan
(`ssd_in_proj`, `ssd_conv`, `ssd_gate_norm`, `ssd_out_proj`): what the layer
does round its scan."""
from benchmarks.harness import nemotron_cost


def Read(run):
  return nemotron_cost.ScopeMs(run, *nemotron_cost.SSD_MIXER)
