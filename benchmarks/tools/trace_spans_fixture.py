#!/usr/bin/env python3
"""Cuts a recorded .xplane.pb down to the small trace that
tests/benchmark/test_spans.py keeps: the program's `lingvo/` spans by thread,
and the first device's ops with the op_name of each, over a few
executions of the step program, in the plain form
benchmarks/harness/spans.py reads.

  python3 benchmarks/tools/trace_spans_fixture.py <trace.xplane.pb> <out.json.gz> [steps]

Also prints what the profiler gives for one event of each kind: the stats of
a host span and of a device op (where op_name is carried), the host plane's
thread lines that hold spans, and the ops with no op_name at all (the copies
XLA inserts). Look at that by hand before trusting the grouping on a new
kind of trace.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import spans  # noqa: E402
from benchmarks.harness import xplane  # noqa: E402


def Describe(path: str) -> dict:
  """One raw event of each kind, as the profiler gives it."""
  import jax
  pd = jax.profiler.ProfileData.from_file(path)
  out = {"planes": {}, "host_span_lines": {}, "device_op_examples": [],
         "host_span_example": None}
  for plane in pd.planes:
    out["planes"][plane.name] = [line.name for line in plane.lines][:40]
    if plane.name == xplane.HOST_PLANE:
      for i, line in enumerate(plane.lines):
        names = {}
        for ev in line.events:
          if ev.name.startswith(spans.SPAN_PREFIX):
            names[ev.name] = names.get(ev.name, 0) + 1
            if out["host_span_example"] is None and ev.name.endswith("/step"):
              out["host_span_example"] = {
                  "line": line.name, "name": ev.name,
                  "stats": {k: v for k, v in ev.stats}}
        if names:
          out["host_span_lines"][f"{i}:{line.name}"] = names
    elif xplane.DEVICE_PLANE.match(plane.name) and not out[
        "device_op_examples"]:
      for line in plane.lines:
        if line.name != xplane.OPS_LINE:
          continue
        seen = set()
        for ev in line.events:
          opcode = xplane.Opcode(xplane.ShortName(ev.name))
          if opcode in seen or len(seen) >= 12:
            continue
          seen.add(opcode)
          out["device_op_examples"].append({
              "opcode": opcode, "name": ev.name[:1500],
              "stats": {k: (v if not isinstance(v, str) else v[:300])
                        for k, v in ev.stats}})
  return out


def main(argv):
  src, dst = argv[1], argv[2]
  steps = int(argv[3]) if len(argv) > 3 else 3
  print(json.dumps(Describe(src), indent=1, default=str)[:30000])
  plain = spans.LoadPb(src)
  step = xplane.StepWindow({"/device:TPU:0": {
      xplane.MODULES_LINE: plain["modules"]}})
  # from the second execution on: the first was launched before the trace
  # began, so the host spans around it were never recorded
  runs = sorted((s, s + d) for n, s, d in plain["modules"]
                if n == step["name"])[1:steps + 1]
  w0, w1 = runs[0][0], runs[-1][1]
  no_name: dict[str, float] = {}
  for n, _, d, op_name in plain["ops"]:
    if not op_name:
      no_name[xplane.Opcode(n)] = no_name.get(xplane.Opcode(n), 0.0) + d
  print("ops with no op_name, ns by opcode:", json.dumps(no_name))
  small = {
      "spans": [[t, n, s - w0, d, a] for t, n, s, d, a in plain["spans"]
                if s + d > w0 and s < w1],
      "ops": [[n, s - w0, d, on] for n, s, d, on in plain["ops"]
              if s >= w0 and s + d <= w1],
      "modules": [[n, s - w0, d] for n, s, d in plain["modules"]
                  if s >= w0 and s + d <= w1]}
  spans.Save(small, dst)
  print(dst, os.path.getsize(dst), "bytes",
        {k: len(v) for k, v in small.items()})


if __name__ == "__main__":
  main(sys.argv)
