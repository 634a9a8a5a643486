#!/usr/bin/env python3
"""Per-request latency report from an exported serving trace.

Input: the Chrome trace-event JSON written by
`TraceRecorder.Export(path)` (lingvo_tpu/observe/trace.py). The file is
Perfetto-openable; this tool consumes the extra top-level `perRequest`
key (ignored by trace viewers) and prints:

- a per-request table: slot, prompt/output tokens, pages, queue wait,
  TTFT, per-output-token latency, total, finish reason;
- aggregate TTFT / TPOT / total-latency p50/p99;
- a queue-wait histogram (how long requests sat before admission);
- where the file holds `perStep` (the engine's step records): the phase
  table of the engine loop, median and p95 of each `lingvo/serve/*`
  phase, of `loop`, and of the host's time between one step's results and
  the next step's launch; and the stalled steps, each with the seconds
  that compiled inside it and the programs' names (`compile_s`).

With MULTIPLE trace files (one per serving replica) it prints a merged
per-replica latency table instead — one row per file plus a fleet row
computed over the union of requests.

Usage:
  python tools/trace_report.py /tmp/serving_trace.json
  python tools/trace_report.py /tmp/replica_a.json /tmp/replica_b.json
"""

from __future__ import annotations

import json
import sys

import numpy as np


def LoadTrace(path: str) -> dict:
  with open(path) as f:
    trace = json.load(f)
  if "perRequest" not in trace:
    raise ValueError(
        f"{path}: no perRequest key — not a TraceRecorder.Export file")
  return trace


def _Percentiles(values) -> dict:
  vals = [v for v in values if v is not None]
  if not vals:
    return {"n": 0}
  arr = np.asarray(vals, np.float64)
  return {
      "n": int(arr.size),
      "p50_ms": round(float(np.percentile(arr, 50)) * 1e3, 3),
      "p99_ms": round(float(np.percentile(arr, 99)) * 1e3, 3),
      "mean_ms": round(float(arr.mean()) * 1e3, 3),
      "max_ms": round(float(arr.max()) * 1e3, 3),
  }


def _QueueWaitHistogram(waits, n_buckets: int = 8) -> list:
  """[(upper_bound_ms, count)] over the observed queue-wait range."""
  vals = np.asarray([w for w in waits if w is not None], np.float64)
  if vals.size == 0:
    return []
  hi = max(float(vals.max()), 1e-6)
  bounds = np.linspace(hi / n_buckets, hi, n_buckets)
  out = []
  prev = 0.0
  for b in bounds:
    n = int(np.sum((vals > prev) & (vals <= b))) + (
        int(np.sum(vals == 0.0)) if prev == 0.0 else 0)
    out.append((round(b * 1e3, 3), n))
    prev = b
  return out


def Summary(trace: dict) -> dict:
  """Aggregate metrics from a loaded trace dict."""
  reqs = list(trace["perRequest"].values())
  return {
      "requests": len(reqs),
      "complete": sum(1 for r in reqs if r.get("total_s") is not None),
      "tokens": sum(r.get("tokens", 0) for r in reqs),
      "ttft": _Percentiles([r.get("ttft_s") for r in reqs]),
      "tpot": _Percentiles([r.get("tpot_s") for r in reqs]),
      "total": _Percentiles([r.get("total_s") for r in reqs]),
      "queue_wait": _Percentiles([r.get("queue_wait_s") for r in reqs]),
      "queue_wait_hist_ms": _QueueWaitHistogram(
          [r.get("queue_wait_s") for r in reqs]),
  }


# the host's work between one step's results arriving and the next step's
# launch: this step's tail, the loop's turn-around, the next step's head
_HOST_HEAD = ("lock_wait", "admit", "build", "draft", "h2d", "dispatch")


_STALL_FACTOR = 2.0     # a period over this many median periods is a stall
_STALLS_KEPT = 20       # the longest of them are listed


def StepSummary(trace: dict) -> dict:
  """Median and p95 (ms) over the file's step records: the step span, the
  loop's turn-around, each phase, and `host`: commit of step n, loop, and
  the phases of step n + 1 up to its launch. {} without perStep."""
  steps = trace.get("perStep") or []
  if not steps:
    return {}

  def _P(values):
    arr = np.asarray(values, np.float64) * 1e3
    return {"p50": round(float(np.percentile(arr, 50)), 4),
            "p95": round(float(np.percentile(arr, 95)), 4)}

  host = [a["phases_s"]["commit"] + b["loop_s"]
          + sum(b["phases_s"][k] for k in _HOST_HEAD)
          for a, b in zip(steps, steps[1:])]
  return {
      "steps": len(steps),
      "span_ms": _P([s["span_s"] for s in steps]),
      "loop_ms": _P([s["loop_s"] for s in steps]),
      "phases_ms": {k: _P([s["phases_s"][k] for s in steps])
                    for k in steps[0]["phases_s"]},
      "host_ms": _P(host) if host else {"p50": 0.0, "p95": 0.0},
  }


def _StepTable(trace: dict) -> list:
  s = StepSummary(trace)
  if not s:
    return []
  lines = ["", f"engine steps: {s['steps']}",
           f"  {'phase':<12} {'p50_ms':>10} {'p95_ms':>10}"]
  rows = [("step span", s["span_ms"]), ("loop", s["loop_ms"])]
  rows += list(s["phases_ms"].items())
  rows.append(("host n->n+1", s["host_ms"]))
  for name, p in rows:
    lines.append(f"  {name:<12} {p['p50']:>10.3f} {p['p95']:>10.3f}")
  stalled = StalledSteps(trace)
  if stalled:
    lines += ["", f"stalled steps (period over {_STALL_FACTOR:g} medians, or "
              "a compile):",
              f"  {'step':>8} {'period_ms':>10} {'compile_ms':>10}  compiled"]
    for r in stalled:
      lines.append(f"  {r['step']:>8} {r['period_ms']:>10.3f} "
                   f"{r['compile_ms']:>10.3f}  "
                   f"{', '.join(r['compile_fun_names']) or '-'}")
  return lines


def StalledSteps(trace: dict) -> list:
  """The steps whose period (loop + span) is over _STALL_FACTOR median
  periods, and every step that compiled (`compile_s`: a late compile is a
  stall with a name, any other a stop of the machine or the host), the
  _STALLS_KEPT longest, longest first. A file from before the key reads 0."""
  steps = trace.get("perStep") or []
  if not steps:
    return []
  periods = [s["loop_s"] + s["span_s"] for s in steps]
  limit = _STALL_FACTOR * float(np.median(periods))
  rows = [{"step": s["step"], "period_ms": p * 1e3,
           "compile_ms": s.get("compile_s", 0.0) * 1e3,
           "compile_fun_names": s.get("compile_fun_names", [])}
          for s, p in zip(steps, periods)
          if p > limit or s.get("compile_s", 0.0) > 0]
  return sorted(rows, key=lambda r: -r["period_ms"])[:_STALLS_KEPT]


def _Ms(v) -> str:
  return "-" if v is None else f"{v * 1e3:.2f}"


def Report(trace: dict) -> str:
  """The human-readable report (table + percentiles + histogram)."""
  reqs = sorted(trace["perRequest"].items(), key=lambda kv: int(kv[0]))
  header = (f"{'req':>5} {'slot':>4} {'prompt':>6} {'tokens':>6} "
            f"{'pages':>5} {'queue_ms':>9} {'ttft_ms':>9} {'tpot_ms':>9} "
            f"{'total_ms':>9}  reason")
  lines = [header, "-" * len(header)]
  for rid, r in reqs:
    lines.append(
        f"{rid:>5} {str(r.get('slot', '-')):>4} "
        f"{r.get('prompt_tokens', 0):>6} {r.get('tokens', 0):>6} "
        f"{r.get('pages', 0):>5} {_Ms(r.get('queue_wait_s')):>9} "
        f"{_Ms(r.get('ttft_s')):>9} {_Ms(r.get('tpot_s')):>9} "
        f"{_Ms(r.get('total_s')):>9}  {r.get('finish_reason') or 'open'}")
  s = Summary(trace)
  lines.append("")
  for name in ("ttft", "tpot", "total", "queue_wait"):
    p = s[name]
    if p.get("n"):
      lines.append(f"{name:>10}: p50 {p['p50_ms']} ms   p99 {p['p99_ms']} "
                   f"ms   mean {p['mean_ms']} ms   (n={p['n']})")
  hist = s["queue_wait_hist_ms"]
  if hist:
    lines.append("")
    lines.append("queue wait histogram:")
    peak = max(n for _, n in hist) or 1
    for bound, n in hist:
      bar = "#" * round(40 * n / peak)
      lines.append(f"  <= {bound:>9.3f} ms  {n:>4}  {bar}")
  lines.extend(_StepTable(trace))
  return "\n".join(lines)


def MergedReport(traces: dict) -> str:
  """Per-replica latency table over {label: trace dict} + a fleet row.

  Each row is that replica's Summary(); the fleet row recomputes the
  percentiles over the UNION of all requests (percentiles don't merge
  from per-replica percentiles)."""
  header = (f"{'replica':<24} {'reqs':>5} {'tokens':>7} "
            f"{'ttft_p50':>9} {'ttft_p99':>9} {'tpot_p50':>9} "
            f"{'tpot_p99':>9} {'total_p50':>10} {'total_p99':>10}")
  lines = [header, "-" * len(header)]

  def _Row(label, reqs):
    ttft = _Percentiles([r.get("ttft_s") for r in reqs])
    tpot = _Percentiles([r.get("tpot_s") for r in reqs])
    total = _Percentiles([r.get("total_s") for r in reqs])

    def _P(p, k):
      return f"{p[k]:.2f}" if p.get("n") else "-"

    return (f"{label:<24} {len(reqs):>5} "
            f"{sum(r.get('tokens', 0) for r in reqs):>7} "
            f"{_P(ttft, 'p50_ms'):>9} {_P(ttft, 'p99_ms'):>9} "
            f"{_P(tpot, 'p50_ms'):>9} {_P(tpot, 'p99_ms'):>9} "
            f"{_P(total, 'p50_ms'):>10} {_P(total, 'p99_ms'):>10}")

  union = []
  for label in sorted(traces):
    reqs = list(traces[label]["perRequest"].values())
    union.extend(reqs)
    lines.append(_Row(label, reqs))
  lines.append("-" * len(header))
  lines.append(_Row("FLEET", union))
  lines.append("")
  lines.append("(latencies in ms; fleet percentiles computed over the "
               "union of requests)")
  return "\n".join(lines)


def main(argv=None) -> int:
  argv = sys.argv[1:] if argv is None else argv
  if not argv:
    print(__doc__, file=sys.stderr)
    return 2
  if len(argv) == 1:
    print(Report(LoadTrace(argv[0])))
    return 0
  print(MergedReport({path: LoadTrace(path) for path in argv}))
  return 0


if __name__ == "__main__":
  sys.exit(main())
