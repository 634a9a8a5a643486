#!/usr/bin/env python3
"""Runs one cell of BENCHMARK.json several times, each run a process of its
own (as the driver's check runs them), and says whether the set is steady.

  python3 benchmarks/tools/sets.py --workload <name> --seeds 11,12,13 [--seconds 30] [--trace 0] [--tag A]

Prints one row per run (its end-to-end readings, `correct`, and for a serving
cell where its window went: steps in the window, the median step period,
stalled seconds, the seconds in which the whole process stood still, the
collector's long passes; for every cell set-up's wall time and the chip's
bring-up, which `setup_s` leaves out of it) and then, for
every end-to-end metric of the cell, the median, the range, the range with the
farthest run left out (readings.RangeLeavingOneOut: the driver's rule) and the
cell's bound beside them. Every run's whole output goes to
<out>/<tag>_<workload>_<seed>.log and its notes to <out>/bench_<tag>/, under
chiprun_out/ unless --out says otherwise. This process never touches JAX: a
chip belongs to the run that is measured.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import readings  # noqa: E402
from benchmarks.harness import spec  # noqa: E402


def _Notes(lines) -> dict:
  out = {}
  for ln in lines:
    if ln.startswith('{"note"'):
      try:
        d = json.loads(ln)
      except ValueError:
        continue
      out[d["note"]] = d["value"]
  return out


def RunOnce(workload, seed, seconds, trace, out_dir, tag, rehearse=False,
            extra=()) -> dict:
  """One run in its own process; {"rc", "line", "notes"}. `extra`: further
  words for run.py's command line (tools/sweep.py's --traffic-override)."""
  cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace), "--out",
         os.path.join(out_dir, "bench_" + tag)]
  if rehearse:
    cmd.append("--rehearse")
  cmd.extend(extra)
  log = os.path.join(out_dir, f"{tag}_{workload}_{seed}_t{trace}.log")
  with open(log, "w") as f:
    rc = subprocess.run(cmd, cwd=ROOT, stdout=f,
                        stderr=subprocess.STDOUT).returncode
  if trace:     # read by the run itself; tens of megabytes a run
    shutil.rmtree(os.path.join(out_dir, "bench_" + tag, "trace_" + workload),
                  ignore_errors=True)
  with open(log) as f:
    lines = [ln.rstrip("\n") for ln in f if ln.strip()]
  line = None
  if rc == 0 and lines:
    try:
      line = json.loads(lines[-1])
    except ValueError:
      pass
  return {"rc": rc, "line": line, "notes": _Notes(lines)}


def Row(seed, res) -> dict:
  """What one run says, flat: readings, correctness, where the window went."""
  line, notes = res["line"] or {}, res["notes"]
  row = {"seed": seed, "rc": res["rc"], "correct": line.get("correct"),
         "failed": line.get("failed")}
  for k, v in (line.get("metrics") or {}).items():
    row[k] = v["value"]
  st = notes.get("step_stalls")
  if st:
    row.update(steps=st["steps"],
               period_ms=round(st["period_ms_median"], 2),
               steps_x_median_s=round(st["steps_x_median_s"], 2),
               stalls=st["stalls"],
               stall_excess_s=round(st["stall_excess_s"], 3),
               slow_excess_s=round(st["slow_excess_s"], 3),
               h2d_ms=st["phases_ms"]["h2d"]["p50"])
  if "client_gaps" in notes:
    # long passes of the client in which the whole process used under half
    # a core: the machine stood still; in the window, and in the lead-in
    still = [(at, ms) for at, ms, _, process_ms in notes["client_gaps"][
        "at_s_ms_thread_cpu_ms_process_cpu_ms"] if process_ms < 0.5 * ms]
    row["stood_still_s"] = round(sum(ms for at, ms in still if at >= 0) / 1e3,
                                 3)
    row["stood_still_lead_in_s"] = round(
        sum(ms for at, ms in still if at < 0) / 1e3, 3)
  if "setup" in notes:
    # set-up's wall time, and what `setup_s` leaves out of it: the chip's
    # bring-up (the first jax.devices())
    for k in ("setup_wall_s", "runtime_start_s"):
      row[k] = round(notes["setup"][k], 3)
  if "compile" in notes:
    row["compile_s"] = round(notes["compile"]["seconds"], 3)
  if "serve_tok_s_between_finishes" in notes:
    win = notes["serve_tok_s_between_finishes"]
    row.update(opened_s=round(win["t_open"], 3),
               window_s=round(win["seconds"], 3), finished=win["finished"])
  if "gc" in notes:
    row["gc_long"] = len(notes["gc"]["long_at_s_generation_ms"])
  if "closed_loop_cycles" in notes:
    row["cycles"] = notes["closed_loop_cycles"]
  if "compiles_in_window" in notes:
    row["compiles_in_window"] = len(notes["compiles_in_window"])
  return row


def Summary(cell, rows) -> list[dict]:
  out = []
  for m in cell["end_to_end"]:
    vals = [r[m["name"]] for r in rows if m["name"] in r]
    if not vals:
      continue
    median, full, left = readings.RangeLeavingOneOut(vals)
    out.append({"metric": m["name"], "unit": m["unit"], "runs": len(vals),
                "median": median, "range": full, "range_share": full / median,
                "range_one_left_out": left,
                "range_one_left_out_share": left / median,
                "bound": m["bound"],
                "under_bound": left / median < m["bound"]})
  return out


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seeds", required=True,
                  help="comma-separated, one run each")
  ap.add_argument("--seconds", type=float, default=None)
  ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
  ap.add_argument("--rehearse", action="store_true",
                  help="on the CPU at tiny sizes: counts, no metric")
  ap.add_argument("--tag", default="set")
  ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
  args = ap.parse_args(argv)
  bench = spec.LoadBenchmark()
  cell = spec.Cell(bench, args.workload)
  seconds = args.seconds if args.seconds else bench["run_seconds"]
  os.makedirs(args.out, exist_ok=True)
  rows = []
  for seed in [int(s) for s in args.seeds.split(",") if s]:
    res = RunOnce(args.workload, seed, seconds, args.trace, args.out,
                  args.tag, args.rehearse)
    rows.append(Row(seed, res))
    print(json.dumps({"run": rows[-1]}), flush=True)
  summary = Summary(cell, rows)
  for s in summary:
    print(json.dumps({"summary": s}), flush=True)
  with open(os.path.join(args.out, f"{args.tag}_{args.workload}.set.json"),
            "w") as f:
    json.dump({"workload": args.workload, "seconds": seconds,
               "trace": args.trace, "rows": rows, "summary": summary}, f,
              indent=1)
  bad = [r for r in rows if r["rc"] != 0 or (
      not args.trace and (not r["correct"] or r["failed"]))]
  return 1 if bad else 0


if __name__ == "__main__":
  sys.exit(main())
