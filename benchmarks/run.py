#!/usr/bin/env python3
"""Runs one cell of BENCHMARK.json once.

  python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the contract's JSON object and nothing
else is put on it; loop series, client counts and the correctness detail go on
earlier lines (one JSON object each, under "note") and into
<out>/<workload>.notes.jsonl. --trace 0 reports the cell's end-to-end
metrics, --trace 1 its per-layer metrics with `busy_s`, `window_s` and a
`breakdown` from the profiler's trace. Every number `correct` was decided on
stands beside its limit under `compared`, the line's last key, and on the
last lines of standard error.

`setup_s` is the wall time from the process's start to the end of set-up less
the chip's bring-up: the first jax.devices(), in which the machine that holds
the chip stands still for seconds that differ from run to run (PERF.md
section 6, PR 31). JAX's import is counted: it is done here, before that
call, whatever the program imports and when. The line says what was left
out, under `setup`.

--rehearse runs the same command line on the CPU at the configuration's tiny
`rehearsal` sizes (kernels in interpret mode, virtual devices for a mesh). It
prints counts only: its last line carries no metric, since a CPU run is never
written under the name of a device metric. Without it a missing TPU is an
error: exit code 3 and no result line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import spec  # noqa: E402  (no JAX in it)


class Ctx:
  """One run's arguments and what the cell code leaves for the line."""

  def __init__(self, cell, args, out_dir, compile_clock):
    self.cell = cell
    self.seed = args.seed
    self.seconds = float(args.seconds)
    self.trace = bool(args.trace)
    self.rehearse = args.rehearse
    self.out_dir = out_dir
    self.trace_dir = os.path.join(out_dir, "trace_" + cell["name"])
    self.t_process = T_PROCESS
    self.t_setup_end = self.t_runtime = None
    self.t_gen0 = self.t_win0 = self.t_win1 = self.t_trace0 = None
    self.trace_started = False
    self.compile_clock = compile_clock
    self.notes = {}

  def SetupEnds(self, t_end: float) -> None:
    """Set-up ended at `t_end` (time.perf_counter)."""
    self.t_setup_end = t_end

  def Setup(self) -> dict:
    """`setup_s` and what it leaves out of set-up's wall time: the first
    jax.devices(), `runtime_start_s` long from `runtime_start_at_s` on."""
    t0, t1 = self.t_process, self.t_setup_end
    r0, r1 = self.t_runtime
    return {"setup_s": (t1 - t0) - (r1 - r0), "setup_wall_s": t1 - t0,
            "runtime_start_s": r1 - r0, "runtime_start_at_s": r0 - t0}

  def Note(self, key, value, quiet=False):
    """Into <out>/<workload>.notes.jsonl and, unless `quiet` (a series too
    long to read in a log), on a line of standard output."""
    self.notes[key] = value
    if not quiet:
      print(json.dumps({"note": key, "value": value}, default=str), flush=True)


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--seconds", type=float, default=10.0)
  ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
  ap.add_argument("--rehearse", action="store_true")
  ap.add_argument("--out", default=os.path.join(ROOT, "bench_out"))
  ap.add_argument("--traffic-override", default="", help="JSON object laid "
                  "over the cell's traffic file: for benchmarks/tools/ "
                  "(a sweep of the rate), never for a run that is reported")
  return _Run(ap.parse_args(argv))


def _Run(args) -> int:
  bench = spec.LoadBenchmark()
  cell = spec.Cell(bench, args.workload)
  if args.traffic_override:
    cell["traffic"] = dict(cell["traffic"],
                           **json.loads(args.traffic_override))
  if args.rehearse:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if cell["chips"] > 1:
      os.environ["XLA_FLAGS"] = (
          os.environ.get("XLA_FLAGS", "") +
          f" --xla_force_host_platform_device_count={cell['chips']}")
  os.makedirs(args.out, exist_ok=True)

  import lingvo_tpu  # noqa: F401  the system under test; absent -> exit 1
  import jax  # noqa: F401  counted in setup_s, whether or not the program's
  #             own import has loaded it already; only the bring-up is not
  from benchmarks.harness import device
  t_runtime0 = time.perf_counter()      # every import ends here
  try:
    dev = device.Require(cell["chips"], args.rehearse)
  except device.NoDevice as e:
    print(f"benchmarks/run.py: {e}", file=sys.stderr)
    return 3
  t_runtime1 = time.perf_counter()
  cache_dir = device.ConfigureCache()
  clock = device.CompileClock()
  ctx = Ctx(cell, args, args.out, clock)
  ctx.t_runtime = (t_runtime0, t_runtime1)
  ctx.Note("run", {"workload": cell["name"], "seed": args.seed,
                   "seconds": args.seconds, "trace": args.trace,
                   "rehearse": args.rehearse, "cache_dir": cache_dir,
                   "device": dev})
  if ctx.trace:
    import shutil
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)

  kind = cell["traffic"]["kind"]
  if kind == "train":
    from benchmarks.harness import train_cell as cell_lib
  elif kind == "serve":
    from benchmarks.harness import serve_cell as cell_lib
  else:
    raise ValueError(f"traffic kind {kind!r}")
  result = cell_lib.Run(ctx)
  ctx.Note("compile", {"seconds": clock.seconds, "cache_hits": clock.hits,
                       "cache_misses": clock.misses})

  run = result["run"]
  run["compile_s"] = clock.seconds
  setup = ctx.Setup()
  ctx.Note("setup", setup)
  run["setup_s"] = setup_s = setup["setup_s"]
  dev["memory_peak_bytes"] = device.MemoryPeakBytes(cell["chips"])
  line = {"correct": bool(result["correct"]),
          "attempted": int(result["attempted"]),
          "failed": int(result["failed"]), "metrics": {}, "device": dev}
  if args.rehearse:
    line["rehearsal"] = True
    line["counts"] = {k: v for k, v in ctx.notes.items()
                      if k in ("offered", "client", "window_loops",
                               "warmup_loops", "correct_detail")}
  elif ctx.trace:
    from benchmarks.harness import peaks
    from benchmarks.harness import xplane
    run["peak"] = peaks.PeakOf(dev["kind"])
    trace = xplane.LoadXplane(xplane.FindXplane(ctx.trace_dir))
    step = xplane.StepWindow(trace)
    red = xplane.Reduce(trace, window=step["window"])
    run["trace"], run["trace_step"] = red, step
    ctx.Note("trace", {"step_module": step["name"], "steps": step["count"],
                       "mean_step_s": step["mean_s"],
                       "window_s": red["window_s"], "busy_s": red["busy_s"],
                       "kernel_s": red["kernel_s"],
                       "per_device": red["per_device"]})
    line["metrics"] = spec.ReadLayerMetrics(cell, run)
    dev["busy_s"] = red["busy_s"]
    dev["window_s"] = red["window_s"]
    line["breakdown"] = {"device_ops": red["ops"],
                         "idle_gaps": red["idle_gaps"]}
  else:
    values = dict(result["end_to_end"], setup_s=setup_s)
    for m in cell["end_to_end"]:
      if m["name"] in values:
        line["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
  line["setup"] = {k: setup[k] for k in ("setup_wall_s", "runtime_start_s")}
  line["compared"] = result["compared"]
  with open(os.path.join(args.out, cell["name"] + ".notes.jsonl"), "a") as f:
    f.write(json.dumps({"args": vars(args), "notes": ctx.notes, "line": line},
                       default=str) + "\n")
  sys.stdout.flush()
  for name, c in result["compared"].items():
    print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
          file=sys.stderr)
  sys.stderr.flush()
  print(json.dumps(line), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
