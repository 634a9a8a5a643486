#!/usr/bin/env python3
"""Re-reads recorded docs runs behind other window edges, offline.

  python3 benchmarks/tools/window_offline.py <notes.jsonl> [...] [--opening 64] [--requests 150]

Every serving run keeps `step_completions_from_start` ([seconds since the
clients' start, tokens done, requests finished] after every engine step) in
<out>/<cell>.notes.jsonl. For each recorded run this prints what
readings.FinishWindow reads (i) as the run was read, (ii) with the window
opened by another request than `--opening` (the same `--requests` after it),
(iii) averaged over the 17 openings `--opening` - 8 .. + 8, and then the
range of each over the runs. (A time axis scaled by any factor reads the
same times that factor: the window is work, a closed loop is clocked by its
own steps, and a uniformly faster system runs the same schedule sooner.) A record
that ends before a window does is left out of that column (a run ends with
its window: only runs made with a longer `--seconds` hold the later ones).
No JAX, no chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import readings  # noqa: E402

OPENINGS = (-8, -4, 4, 8)


def Records(path: str):
  """[(seed, [(t, tokens, finished), ...])] of the untraced runs in a file."""
  out = []
  with open(path) as f:
    for ln in f:
      d = json.loads(ln)
      rec = d["notes"].get("step_completions_from_start")
      if rec and len(rec[0]) == 3 and not d["args"]["trace"]:
        out.append((d["args"]["seed"], [tuple(x) for x in rec]))
  return out


def Read(rec, opening: int, requests: int):
  """tok_s; None where the record ends before the window does."""
  try:
    return readings.FinishWindow(rec, opening, requests)["tok_s"]
  except ValueError:
    return None


def Table(records, opening: int, requests: int) -> list[dict]:
  rows = []
  for seed, rec in records:
    base = Read(rec, opening, requests)
    row = {"seed": seed, "tok_s": base}
    for d in OPENINGS:
      v = Read(rec, opening + d, requests)
      row[f"open{d:+d}"] = None if None in (v, base) else v / base - 1
    over = [Read(rec, opening + d, requests) for d in range(-8, 9)]
    row["mean17.tok_s"] = None if None in over else sum(over) / len(over)
    rows.append(row)
  return rows


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("files", nargs="+")
  ap.add_argument("--opening", type=int, default=64)
  ap.add_argument("--requests", type=int, default=150)
  args = ap.parse_args(argv)
  records = [r for path in args.files for r in Records(path)]
  rows = Table(records, args.opening, args.requests)
  for row in rows:
    print(json.dumps({"run": {k: (round(v, 5) if isinstance(v, float) and
                                  not k.endswith("tok_s") else v)
                              for k, v in row.items()}}))
  for key in ("tok_s", "mean17.tok_s"):
    vals = [r[key] for r in rows if r[key] is not None]
    if len(vals) < 2:
      continue
    median, full, left = readings.RangeLeavingOneOut(vals)
    said = {"reading": key, "runs": len(vals), "median": median,
            "range_share": full / median,
            "range_one_left_out_share": left / median}
    if key == "tok_s":
      said["worst_move"] = {
          k: max((abs(r[k]) for r in rows if r[k] is not None), default=None)
          for k in rows[0] if k.startswith("open")}
    print(json.dumps({"summary": said}))
  return 0


if __name__ == "__main__":
  sys.exit(main())
