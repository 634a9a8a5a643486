#!/usr/bin/env python3
"""Sweeps an open-loop serving cell's arrival rate to find its knee: the
highest rate the system sustains. Done once by a benchmark PR, on the chip;
the cell then runs at four fifths of it, a number in its traffic file.

  python3 benchmarks/tools/sweep.py --workload <cell> --rates 1.6,2.1,2.6 [--backlog-ms 500] [--seed 31001] [--tag K]

One run a rate (each a process of its own, through tools/sets.py and run.py's
--traffic-override), one row a run: what was offered and finished, the median
queue wait by thirds of the window (growing thirds: a growing backlog), the
requests still open at the window's end, failed requests, the token gaps and
the times to the first token, the engine's median step and the tokens a step
carried. A rate is sustained where the queue wait does not grow over the
thirds, no request fails and the requests open at the end are what the rate
times a request's life gives (not more than at half the rate, doubled).
The sweep goes up the list of rates until two in a row end with a backlog (a
median queue wait over --backlog-ms in the window's last third), then runs
the three rates round the bend (the highest without a backlog, the one before
and the one after) a second time. The verdict is the reader's, from the rows.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import spec  # noqa: E402
from benchmarks.tools import sets  # noqa: E402


def Row(rate: float, seed: int, res: dict) -> dict:
  line, notes = res["line"] or {}, res["notes"]
  lat = notes.get("latency_summary") or {}
  client = notes.get("client") or {}
  stalls = notes.get("step_stalls") or {}
  row = {"rate_per_s": rate, "seed": seed, "rc": res["rc"],
         "correct": line.get("correct"), "failed": line.get("failed"),
         "attempted": line.get("attempted"),
         "offered": (notes.get("offered") or {}).get("requests"),
         "finished_in_window": client.get("finished_in_window"),
         "itl_gaps": client.get("itl_gaps"),
         "ttft_samples": client.get("ttft_samples"),
         "queue_wait_ms_median_by_third":
             lat.get("queue_wait_ms_median_by_third"),
         "open_at_end": lat.get("open_at_end"),
         "itl_ms_p50_p95_p99": lat.get("itl_ms_p50_p95_p99"),
         "ttft_ms_p50_p95": lat.get("ttft_ms_p50_p95"),
         "step_ms_median": lat.get("step_ms_median"),
         "steps_in_window": lat.get("steps_in_window"),
         "gen_late_ms_p99": lat.get("gen_late_ms_p99"),
         "stall_excess_s": stalls.get("stall_excess_s")}
  tok = notes.get("serve_tok_s_between_steps")
  if tok and lat.get("steps_in_window"):
    row["tokens_per_step"] = round(tok["tokens"] / lat["steps_in_window"], 2)
  for k, v in (line.get("metrics") or {}).items():
    row[k] = v["value"]
  return row


def Backlog(row: dict, backlog_ms: float) -> bool:
  """The run ended with a queue: a failed request, no reading, or a median
  queue wait over `backlog_ms` in the window's last third."""
  thirds = row.get("queue_wait_ms_median_by_third") or [None]
  return bool(row["rc"] != 0 or row["failed"] or thirds[-1] is None
              or thirds[-1] > backlog_ms)


def Bend(rates: list[float], backlog: list[bool]) -> list[float]:
  """The three rates round the bend: the highest that ended without a
  backlog (a lower one that did not met a stop of the machine: the rates
  above it would not have held), the one before it and the one after."""
  clear = [i for i, b in enumerate(backlog) if not b]
  if not clear:
    return rates[:2]
  return rates[max(0, clear[-1] - 1):clear[-1] + 2]


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--rates", required=True, help="comma-separated")
  ap.add_argument("--backlog-ms", type=float, default=500.0)
  ap.add_argument("--seed", type=int, default=3100000301)
  ap.add_argument("--seconds", type=float, default=None)
  ap.add_argument("--tag", default="sweep")
  ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
  args = ap.parse_args(argv)
  seconds = args.seconds or spec.LoadBenchmark()["run_seconds"]
  os.makedirs(args.out, exist_ok=True)
  rows, seed = [], args.seed

  def _One(rate):
    nonlocal seed
    res = sets.RunOnce(
        args.workload, seed, seconds, 0, args.out, args.tag,
        extra=("--traffic-override", json.dumps({"rate_per_s": rate})))
    rows.append(Row(rate, seed, res))
    print(json.dumps({"run": rows[-1]}), flush=True)
    seed += 1
    return rows[-1]

  rates = [float(r) for r in args.rates.split(",") if r]
  backlog = []
  for rate in rates:
    backlog.append(Backlog(_One(rate), args.backlog_ms))
    if backlog[-2:] == [True, True]:
      break
  for rate in Bend(rates[:len(backlog)], backlog):
    _One(rate)
  with open(os.path.join(args.out, f"{args.tag}_{args.workload}.sweep.json"),
            "w") as f:
    json.dump(rows, f, indent=1)
  return 0


if __name__ == "__main__":
  sys.exit(main())
