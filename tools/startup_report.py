#!/usr/bin/env python3
"""Where a process's set-up went, from its own start-up record.

Input, any of:
- a `/statusz` document (its `startup` key: observe.profile.Startup().
  Document()), saved as JSON;
- a benchmark run's `<out>/<cell>.notes.jsonl` or its captured standard
  output: the traced run's notes `startup` (the same document),
  `startup_tiling` (the seven set-up metrics, which add up to `setup_s`, and
  what of them compiled in the ramp behind set-up's end) and
  `window_compile`; a notes file from a run that printed no such note still
  gives the seven numbers of its result line.

Prints the phase table (`lingvo/setup/*`: start, end and seconds from the
record's zero, the phase round each, and what lies before, between and
after the outermost phases), the per-program table (what each named
program's compile was made of: trace / lower / compile / fetch, `cache_hit`,
thread), the largest compile events under no named program, the seconds that
compiled inside engine steps and train loops, and the tiling.

Usage:
  python tools/startup_report.py /tmp/statusz.json
  python tools/startup_report.py bench_out/dense1b_serve_chat.notes.jsonl
"""

from __future__ import annotations

import json
import sys

_TILING_KEYS = ("setup_build_s", "setup_step_trace_s", "setup_step_lower_s",
                "setup_step_compile_s", "setup_other_programs_s",
                "setup_first_steps_s", "setup_unnamed_s")


def Load(path: str) -> list[dict]:
  """One {"startup", "tiling", "window_compile"} a run the file holds
  (a key is None where the file has no such note)."""
  with open(path) as f:
    text = f.read()
  try:
    doc = json.loads(text)
    if isinstance(doc, dict) and "startup" in doc:     # /statusz
      return [{"startup": doc["startup"], "tiling": None,
               "window_compile": None}]
  except json.JSONDecodeError:
    pass
  runs, notes = [], {}
  for ln in text.splitlines():
    try:
      obj = json.loads(ln)
    except json.JSONDecodeError:
      continue
    if not isinstance(obj, dict):
      continue
    if "note" in obj:                                  # standard output
      notes[obj["note"]] = obj["value"]
      continue
    line = obj.get("line", obj if "metrics" in obj else None)
    if line is None:
      continue
    notes = dict(obj.get("notes", {}), **notes)        # a notes.jsonl line
    tiling = notes.get("startup_tiling")
    if tiling is None:
      got = {k: line["metrics"][k]["value"] for k in _TILING_KEYS
             if k in line.get("metrics", {})}
      tiling = got or None
    runs.append({"startup": notes.get("startup"), "tiling": tiling,
                 "window_compile": notes.get("window_compile")})
    notes = {}
  if notes:                 # notes with no result line behind them
    runs.append({"startup": notes.get("startup"),
                 "tiling": notes.get("startup_tiling"),
                 "window_compile": notes.get("window_compile")})
  return runs


def PhaseRows(phases: list[dict]) -> list[dict]:
  """The phases in order of their start, each with its depth, and a row
  `(between)` for what lies before and between the outermost ones."""
  rows, cursor = [], 0.0
  for p in sorted(phases, key=lambda p: (p["start_s"], -p["end_s"])):
    depth, parent = 0, p["parent"]
    while parent is not None and depth < 8:
      depth += 1
      parent = next((q["parent"] for q in phases if q["phase"] == parent
                     and q["start_s"] <= p["start_s"] <= q["end_s"]), None)
    if depth == 0:
      if p["start_s"] > cursor + 5e-4:
        rows.append({"phase": "(between)", "depth": 0, "start_s": cursor,
                     "end_s": p["start_s"], "thread": ""})
      cursor = max(cursor, p["end_s"])
    rows.append(dict(p, depth=depth))
  return rows


def Report(run: dict) -> str:
  lines = []
  startup = run["startup"]
  if startup:
    lines += [f"{'phase':<24} {'start_s':>9} {'end_s':>9} {'seconds':>9}  "
              "thread"]
    for r in PhaseRows(startup["phases"]):
      name = "  " * r["depth"] + r["phase"]
      lines.append(f"{name:<24} {r['start_s']:>9.3f} {r['end_s']:>9.3f} "
                   f"{r['end_s'] - r['start_s']:>9.3f}  {r['thread']}")
    lines += ["", f"{'program':<28} {'at_s':>8} {'wall_s':>8} {'trace_s':>8} "
              f"{'lower_s':>8} {'compile_s':>9} {'fetch_s':>8} {'hit':>5}  "
              "thread"]
    for r in startup["programs"]:
      lines.append(
          f"{r['program']:<28} {r['at_s']:>8.3f} {r['compile_wall_s']:>8.3f} "
          f"{r['trace_s']:>8.3f} {r['lower_s']:>8.3f} {r['backend_s']:>9.3f} "
          f"{r['fetch_s']:>8.3f} {str(r['cache_hit']):>5}  {r['thread']}")
    other = startup["other_programs"]
    lines += ["", f"under no named program: {other['seconds']:.3f} s in "
              f"{other['events']} events (the whole record; "
              f"{startup['events_dropped']} events dropped)"]
    for name, seconds, count, *kinds in other["top"]:
      made = ", ".join(f"{k} {x:.3f}" for k, x in (kinds[0] if kinds
                                                   else {}).items())
      lines.append(f"  {seconds:>9.3f} s {count:>6} x  {name}"
                   + (f"  ({made})" if made else ""))
    inside = startup.get("inside")
    if inside:
      lines += ["", "compiled inside an engine step: "
                f"{inside['step']:.3f} s; inside a train loop: "
                f"{inside['loop']:.3f} s (the first steps' and any late "
                "compile's)"]
  tiling = run["tiling"]
  if tiling:
    lines += ["", "set-up, tiled (the seven add up to setup_s):"]
    for k in _TILING_KEYS:
      if k in tiling:
        lines.append(f"  {k:<26} {tiling[k]:>9.3f}")
    for k in ("setup_s", "overlap_s"):
      if k in tiling:
        lines.append(f"  {k:<26} {tiling[k]:>9.3f}")
    ramp = tiling.get("ramp_compile_s")
    if ramp is not None:
      lines.append(
          f"  of these, compiled in the ramp between set-up's end and the "
          f"window: {sum(ramp.values()):.3f}"
          + "".join(f"  {k} {v:.3f}" for k, v in ramp.items() if v))
  wc = run["window_compile"]
  if wc:
    lines += ["", f"compiled inside the window: {wc['compile_s']:.3f} s in "
              f"{wc['count']} steps or loops"]
    for r in wc["rows"]:
      lines.append(f"  {json.dumps(r)}")
  return "\n".join(lines) if lines else "no start-up record in this run"


def main(argv=None) -> int:
  argv = sys.argv[1:] if argv is None else argv
  if len(argv) != 1:
    print(__doc__, file=sys.stderr)
    return 2
  runs = Load(argv[0])
  if not runs:
    print(f"{argv[0]}: no /statusz document, run line or note", file=sys.stderr)
    return 1
  for i, run in enumerate(runs):
    if len(runs) > 1:
      print(f"== run {i + 1} of {len(runs)}")
    print(Report(run))
  return 0


if __name__ == "__main__":
  sys.exit(main())
