#!/usr/bin/env python3
"""Cuts a recorded .xplane.pb down to the small trace the tests keep:
the device planes' 'XLA Ops' and 'XLA Modules' lines over the first few
executions of the step program, and the host events of 5 us or more that
overlap them, in the plain form benchmarks/harness/xplane.py reads.

  python3 benchmarks/tools/trace_fixture.py <trace.xplane.pb> <out.json.gz> [steps]

Also prints what the trace holds (planes, lines, longest events): look at
that by hand before trusting the reduction on a new kind of trace.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import xplane  # noqa: E402


def main(argv):
  src, dst = argv[1], argv[2]
  steps = int(argv[3]) if len(argv) > 3 else 2
  trace = xplane.LoadXplane(src)
  print(json.dumps(xplane.Describe(trace), indent=1)[:20000])
  step = xplane.StepWindow(trace)
  w0 = step["window"][0]
  w1 = sorted(step["ends"])[min(steps, len(step["ends"])) - 1]
  small = {}
  for plane, lines in trace.items():
    keep = {}
    for name, evs in lines.items():
      if xplane.DEVICE_PLANE.match(plane) and name not in (
          xplane.OPS_LINE, xplane.MODULES_LINE):
        continue
      floor = 5000.0 if plane == xplane.HOST_PLANE else 0.0
      cut = [[n, s - w0, d] for n, s, d in evs
             if s >= w0 and s + d <= w1 and d >= floor]
      if cut:
        keep[name] = cut
    small[plane] = keep
  xplane.Save(small, dst)
  print(dst, os.path.getsize(dst), "bytes",
        {p: {ln: len(e) for ln, e in ls.items()} for p, ls in small.items()})


if __name__ == "__main__":
  main(sys.argv)
