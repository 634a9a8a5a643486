"""The host's time between steps (step_host_ms) summed over the window, over
the summed step periods: the host's estimate of the device's idle share."""
from benchmarks.harness import spans

Read = spans.StepHostShare
