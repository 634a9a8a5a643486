"""Reduction of a profiler trace (.xplane.pb) to busy/idle time, per-op time,
kernel time and exposed collective time. Benchmark code: every PR reduces the
same way. Checked on the small recorded trace
benchmarks/data/trace_train_small.json.gz
by tests/benchmark/test_xplane.py.

A trace is first brought into a plain form (planes -> lines -> events of
(name, start_ns, duration_ns)), which is also the form the recorded fixture is
kept in, and everything else works on that.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)")
KERNEL = "custom-call:tpu_custom_call"   # a Pallas (Mosaic) kernel's op
_HLO = re.compile(r"^(%[^ ]+) = (.*?) ([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_SAFE = re.compile(r"[^A-Za-z0-9_.:-]+")


def FindXplane(logdir: str) -> str:
  paths = sorted(glob.glob(
      os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
  if not paths:
    raise FileNotFoundError(f"no .xplane.pb under {logdir}")
  return paths[-1]


def ShortName(name: str) -> str:
  """A device op's event name is its whole HLO line, kilobytes long. Kept
  here as '<opcode>[:<custom_call_target>] %<name> <result type, cut>': the
  opcode says what the op is (an operand called %custom-call.20 inside a
  fusion's text does not make that fusion a kernel)."""
  m = _HLO.match(name)
  if not m:
    return name[:160]
  lhs, typ, opcode = m.groups()
  if opcode == "custom-call":
    t = _TARGET.search(name)
    opcode += ":" + (t.group(1) if t else "")
  return f"{opcode} {lhs} {typ[:64]}"


def Opcode(short: str) -> str:
  return short.split(" ", 1)[0]


def LoadXplane(path: str, keep_host_events: int = 200000) -> dict:
  """{plane: {line: [[name, start_ns, dur_ns], ...]}} from a .xplane.pb,
  device op names shortened by ShortName."""
  import jax
  pd = jax.profiler.ProfileData.from_file(path)
  out = {}
  for plane in pd.planes:
    if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
      continue
    lines = {}
    for line in plane.lines:
      evs = [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
             for ev in line.events]
      if plane.name == HOST_PLANE:
        evs = evs[:keep_host_events]
      elif line.name == OPS_LINE:
        short = {}
        for ev in evs:
          if ev[0] not in short:
            short[ev[0]] = ShortName(ev[0])
          ev[0] = short[ev[0]]
      if evs:
        lines.setdefault(line.name, []).extend(evs)
    out[plane.name] = lines
  return out


def Save(trace: dict, path: str) -> None:
  with gzip.open(path, "wt") as f:
    json.dump(trace, f, separators=(",", ":"))


def Load(path: str) -> dict:
  if path.endswith(".pb"):
    return LoadXplane(path)
  with gzip.open(path, "rt") as f:
    return json.load(f)


def Union(intervals) -> list[list[float]]:
  """Sorted disjoint union of [start, end) intervals."""
  out = []
  for s, e in sorted(intervals):
    if e <= s:
      continue
    if out and s <= out[-1][1]:
      out[-1][1] = max(out[-1][1], e)
    else:
      out.append([s, e])
  return out


def SelfTimes(events) -> list[tuple[str, float, float]]:
  """(name, start, self duration) per event of one line: an event's duration
  minus what the events nested inside it cover (a `while` holds its body)."""
  evs = sorted(events, key=lambda e: (e[1], -e[2]))
  out = []
  stack = []   # [name, start, end, child_time]

  def _Pop():
    name, s, e, child = stack.pop()
    out.append((name, s, max(e - s - child, 0.0)))
    if stack:
      stack[-1][3] += e - s

  for name, s, d in evs:
    while stack and s >= stack[-1][2]:
      _Pop()
    stack.append([name, s, s + d, 0.0])
  while stack:
    _Pop()
  return out


def SafeName(name: str, limit: int = 64) -> str:
  return _SAFE.sub("_", name)[:limit]


def _HostEvents(trace: dict):
  host = trace.get(HOST_PLANE, {})
  return [(n, s, s + d) for evs in host.values() for n, s, d in evs if d > 0]


def _NameGaps(busy, w0: float, w1: float, trace: dict, longest: int = 200
              ) -> dict[str, float]:
  """Idle time of one device by the host event under it: each of the longest
  gaps goes to the shortest host event that covers at least half of it (the
  innermost); the rest are summed as short gaps."""
  import numpy as np
  edges = [w0] + [x for iv in busy for x in iv] + [w1]
  gaps = sorted(((ge - gs, gs, ge) for gs, ge in zip(edges[0::2], edges[1::2])
                 if ge > gs), reverse=True)
  host = _HostEvents(trace)
  names = [h[0] for h in host]
  hs = np.asarray([h[1] for h in host], float)
  he = np.asarray([h[2] for h in host], float)
  out: dict[str, float] = {}
  for length, gs, ge in gaps[:longest]:
    name = "host:_no_event"
    if len(host):
      cover = np.minimum(he, ge) - np.maximum(hs, gs)
      under = np.flatnonzero(cover >= 0.5 * length)
      if len(under):
        # the innermost: a thread's outer frames cover every gap, the
        # shortest event over half of this one says what the host was at
        name = names[int(under[np.argmin((he - hs)[under])])]
    out[name] = out.get(name, 0.0) + length
  rest = sum(g[0] for g in gaps[longest:])
  if rest > 0:
    out["short_gaps"] = rest
  return out


def Reduce(trace: dict, window: tuple[float, float] | None = None,
           kernel_opcode: str = KERNEL, top: int = 10) -> dict:
  """All the trace-side numbers of one run.

  window: (start_ns, end_ns) on the trace's clock; default the span from the
  first to the last device op. Returns seconds:
    window_s, busy_s (mean over devices of the union of op intervals),
    per_device [{busy_s, kernel_s, collective_exposed_s}], ops (top self
    times by name, summed over devices / devices), kernel_s,
    collective_exposed_s (means over devices), idle_gaps (longest gaps named
    by the host event that covers most of each), devices.
  """
  planes = sorted(p for p in trace if DEVICE_PLANE.match(p))
  per_dev = []
  op_time: dict[str, float] = {}
  all_ops = []
  for p in planes:
    lines = trace[p]
    ops = lines.get(OPS_LINE)
    if ops is None:
      continue
    all_ops.append((p, ops))
  if not all_ops:
    raise ValueError(f"no '{OPS_LINE}' line on any device plane: "
                     f"{ {p: list(trace[p]) for p in planes} }")
  if window is None:
    w0 = min(e[1] for _, ops in all_ops for e in ops)
    w1 = max(e[1] + e[2] for _, ops in all_ops for e in ops)
  else:
    w0, w1 = window
  gaps_named: dict[str, float] = {}
  for p, ops in all_ops:
    inside = [(n, max(s, w0), min(s + d, w1) - max(s, w0)) for n, s, d in ops
              if s + d > w0 and s < w1]
    busy = Union([(s, s + d) for _, s, d in inside])
    busy_ns = sum(e - s for s, e in busy)
    kernel_ns = coll_ns = 0.0
    for name, _, self_d in SelfTimes(inside):
      op_time[name] = op_time.get(name, 0.0) + self_d
      opcode = Opcode(name)
      if opcode == kernel_opcode:
        kernel_ns += self_d
      if COLLECTIVE.match(opcode):
        coll_ns += self_d
    per_dev.append({"plane": p, "busy_s": busy_ns * 1e-9,
                    "kernel_s": kernel_ns * 1e-9,
                    "collective_exposed_s": coll_ns * 1e-9})
    if p == all_ops[0][0]:
      gaps_named = _NameGaps(busy, w0, w1, trace)
  n_dev = len(per_dev)
  mean = lambda k: sum(d[k] for d in per_dev) / n_dev
  ops_top = sorted(((SafeName(n), t * 1e-9 / n_dev)
                    for n, t in op_time.items()), key=lambda x: -x[1])[:top]
  gaps_top = sorted(((SafeName(n), t * 1e-9) for n, t in gaps_named.items()),
                    key=lambda x: -x[1])[:top]
  return {"devices": n_dev, "window_s": (w1 - w0) * 1e-9,
          "busy_s": mean("busy_s"), "kernel_s": mean("kernel_s"),
          "collective_exposed_s": mean("collective_exposed_s"),
          "per_device": per_dev,
          "ops": [list(x) for x in ops_top],
          "idle_gaps": [list(x) for x in gaps_top]}


def Describe(trace: dict, per_line: int = 5) -> dict:
  """Planes, lines, counts and the longest events of each line: what to look
  at by hand before trusting the reduction on a new kind of trace."""
  out = {}
  for p, lines in trace.items():
    out[p] = {}
    for ln, evs in lines.items():
      longest = sorted(evs, key=lambda e: -e[2])[:per_line]
      out[p][ln] = {"events": len(evs),
                    "longest": [[e[0][:120], e[2]] for e in longest]}
  return out


MODULES_LINE = "XLA Modules"


def StepWindow(trace: dict) -> dict:
  """The step program's executions in the trace: the module with the most
  device time on the first device's 'XLA Modules' line. Returns its name,
  how many whole executions the trace holds, the window from the first one's
  start to the last one's end (ns) and their mean duration (s): a trace
  reduced over that window holds whole steps only."""
  planes = sorted(p for p in trace if DEVICE_PLANE.match(p))
  mods = trace[planes[0]][MODULES_LINE]
  by_name: dict[str, list] = {}
  for n, s, d in mods:
    by_name.setdefault(n, []).append((s, d))
  name, evs = max(by_name.items(), key=lambda kv: sum(d for _, d in kv[1]))
  evs.sort()
  return {"name": name, "count": len(evs), "window": (evs[0][0],
          evs[-1][0] + evs[-1][1]),
          "mean_s": sum(d for _, d in evs) / len(evs) * 1e-9,
          "ends": [s + d for s, d in evs]}
