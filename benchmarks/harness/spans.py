"""What the program's own spans, step records and scope names say about a
run: the readers of step_span_ms, step_host_ms, step_host_share,
step_h2d_ms, step_stall_share, train_host_ms, idle_unspanned_share and
busy_unscoped_share share this, and every serving run's note step_stalls.

Three sources:
- the serving engine's step records (lingvo_tpu.observe.trace: one StepTrace
  per engine step, on time.perf_counter, the clock of run["window"]), found
  through observe.trace.Live() because a reader is handed no engine;
- the `lingvo/...` spans (jax.profiler.TraceAnnotation) on the host plane of
  the traced run's .xplane.pb, by thread line;
- the device ops of the same file with the `op_name` of each, in which
  jax.named_scope left the name of the model block. A TPU trace carries
  op_name neither as an event stat nor in the HLO text that is the event's
  name (the profiler prints it without metadata): it is in the HloProto of
  each module, which the `/host:metadata` plane keeps as a bytes stat of the
  module's event metadata. HloOpNames() reads it there, with a wire-format
  reader of its own (the container has no xplane or hlo protobuf module).

run.py hands readers no trace path, so TracePath() finds the file where
run.py wrote it: <--out>/trace_<--workload>. The pb is loaded here and not
through xplane.LoadXplane, which keeps neither event stats nor thread lines
and cuts the host plane at 200,000 events.

A program without the spans (the parent of the PR that brought them) gives
every reader here nothing to read: they return None and never raise for
that. The plain form (`Load`) is what the recorded fixture
benchmarks/data/trace_spans_small.json.gz keeps.
"""

from __future__ import annotations

import argparse
import functools
import gzip
import json
import math
import os
import re
import statistics
import sys

from benchmarks.harness import device
from benchmarks.harness import readings
from benchmarks.harness import xplane

SPAN_PREFIX = "lingvo/"
# the scope names the program puts on its blocks (docs/observability.md);
# an op belongs to the innermost one in its op_name
SCOPES = ("atten", "ffn", "norm", "embed", "head_loss", "optimizer_update",
          "ragged_attend", "kv_write", "head_sample")
UNSCOPED = "_unscoped"
UNSPANNED = "_no_span"
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?=[/)]|$)")
# the segments of a step before its launch and after its results
# (lingvo_tpu.observe.trace.STEP_SEGMENTS by position)
_DISPATCH, _DEVICE_WAIT = 5, 6
_TRACE_TAIL_S = 6.0      # serve_cell._TRACE_TAIL_S: what a traced run traces
_STALL_FACTOR = 2.0      # a period over this many median periods is a stall
_STALLS_KEPT = 40        # stalled steps a report lists (the longest)


# -- where the traced run's file is -------------------------------------------


def TraceDir(argv=None) -> str:
  """<--out>/trace_<--workload>, from the command line run.py was given
  (its own defaults where an option is left out)."""
  ap = argparse.ArgumentParser(add_help=False)
  ap.add_argument("--workload")
  ap.add_argument("--out", default=os.path.join(device.ROOT, "bench_out"))
  args, _ = ap.parse_known_args(sys.argv[1:] if argv is None else argv)
  if not args.workload:
    raise ValueError("no --workload on the command line: cannot tell where "
                     "run.py wrote the trace")
  return os.path.join(args.out, "trace_" + args.workload)


def TracePath(argv=None) -> str:
  """The traced run's .xplane.pb. FileNotFoundError when there is none: a
  traced run whose trace cannot be found must fail, not lose its metrics."""
  return xplane.FindXplane(TraceDir(argv))


# -- the plain form -----------------------------------------------------------


def _Varint(b, i):
  x = shift = 0
  while True:
    c = b[i]
    i += 1
    x |= (c & 0x7F) << shift
    shift += 7
    if c < 0x80:
      return x, i


def _Fields(b):
  """(field number, wire type, value) of one protobuf message: varints as
  ints, length-delimited fields as bytes (memoryview slices)."""
  i, n = 0, len(b)
  while i < n:
    key, i = _Varint(b, i)
    wire = key & 7
    if wire == 0:
      v, i = _Varint(b, i)
    elif wire == 2:
      size, i = _Varint(b, i)
      v = b[i:i + size]
      i += size
    elif wire == 1:
      v = b[i:i + 8]
      i += 8
    elif wire == 5:
      v = b[i:i + 4]
      i += 4
    else:
      raise ValueError(f"wire type {wire}")
    yield key >> 3, wire, v


def _ModuleOpNames(hlo_proto) -> dict[str, str]:
  """{instruction name: op_name} of one HloProto (hlo_module = 1;
  HloModuleProto.computations = 3; HloComputationProto name = 1,
  instructions = 2, id = 5; HloInstructionProto name = 1, metadata = 7,
  called_computation_ids = 38; OpMetadata.op_name = 2). An instruction
  whose own op_name holds no scope name (a fusion is given the metadata of
  one of the ops it fused, or none) takes the op_name that holds the most
  frequent scope among the instructions of the computations it calls."""
  comps = {}      # computation id -> [op_name of each instruction]
  instrs = []     # (name, op_name, called ids)
  for f, _, mod in _Fields(hlo_proto):
    if f != 1:
      continue
    for f2, _, comp in _Fields(mod):
      if f2 != 3:
        continue
      comp_id, inside = None, []
      for f3, w3, v3 in _Fields(comp):
        if f3 == 5 and w3 == 0:
          comp_id = v3
        elif f3 == 2:
          name, op_name, calls = "", "", []
          for f4, w4, v4 in _Fields(v3):
            if f4 == 1:
              name = bytes(v4).decode()
            elif f4 == 7:
              for f5, _, v5 in _Fields(v4):
                if f5 == 2:
                  op_name = bytes(v5).decode()
            elif f4 == 38:
              if w4 == 0:
                calls.append(v4)
              else:                       # packed
                j = 0
                while j < len(v4):
                  c, j = _Varint(v4, j)
                  calls.append(c)
          instrs.append((name, op_name, calls))
          inside.append(op_name)
      comps[comp_id] = inside
  out = {}
  for name, op_name, calls in instrs:
    if calls and ScopeOf(op_name) == UNSCOPED:
      votes: dict[str, list] = {}
      for c in calls:
        for inner in comps.get(c, ()):
          sc = ScopeOf(inner)
          if sc != UNSCOPED:
            votes.setdefault(sc, []).append(inner)
      if votes:
        op_name = max(votes.values(), key=len)[0]
    out[name] = op_name
  return out


def HloOpNames(xspace: bytes) -> dict[str, dict[str, str]]:
  """{module name as the 'XLA Modules' line has it, 'jit_f(123)':
  {instruction name: op_name}} from the `/host:metadata` plane of a
  serialized XSpace (planes = 1; XPlane name = 2, event_metadata = 4, a map
  whose values are XEventMetadata: name = 2, stats = 5; the HloProto is the
  XStat with a bytes_value = 6)."""
  out = {}
  view = memoryview(xspace)
  for f, _, plane in _Fields(view):
    if f != 1:
      continue
    name = next((bytes(v) for f2, _, v in _Fields(plane) if f2 == 2), b"")
    if name != b"/host:metadata":
      continue
    for f2, _, entry in _Fields(plane):
      if f2 != 4:
        continue
      for f3, _, meta in _Fields(entry):
        if f3 != 2:
          continue
        module, proto = "", None
        for f4, _, v4 in _Fields(meta):
          if f4 == 2:
            module = bytes(v4).decode()
          elif f4 == 5:
            for f5, w5, v5 in _Fields(v4):
              if f5 == 6 and w5 == 2:
                proto = v5
        if proto is not None:
          out[module] = _ModuleOpNames(proto)
  return out


_INSTRUCTION = re.compile(r"^%([^ ]+) = ")


def LoadPb(path: str) -> dict:
  """{"spans": [[thread, name, start_ns, dur_ns, args], ...],
      "ops": [[short name, start_ns, dur_ns, op_name], ...],
      "modules": [[name, start_ns, dur_ns], ...]}
  spans: every `lingvo/` event of the host plane, `thread` the index of its
  line; ops, modules: the first device's 'XLA Ops' and 'XLA Modules' lines,
  each op with the op_name its instruction has in the HloProto of the module
  that was running ("" where it has none, or the file keeps no HloProto)."""
  import bisect
  import jax
  with open(path, "rb") as f:
    raw = f.read()
  op_names = HloOpNames(raw)
  pd = jax.profiler.ProfileData.from_serialized_xspace(raw)
  spans, ops, modules = [], [], []
  first = min((p.name for p in pd.planes if xplane.DEVICE_PLANE.match(p.name)),
              default=None)
  for plane in pd.planes:
    if plane.name == xplane.HOST_PLANE:
      for thread, line in enumerate(plane.lines):
        for ev in line.events:
          if ev.name.startswith(SPAN_PREFIX):
            args = {k: v for k, v in ev.stats
                    if isinstance(v, (int, float, str))}
            spans.append([thread, ev.name, float(ev.start_ns),
                          float(ev.duration_ns), args])
    elif plane.name == first:
      lines = {line.name: line for line in plane.lines}
      if xplane.MODULES_LINE in lines:
        modules = sorted(
            ([ev.name, float(ev.start_ns), float(ev.duration_ns)]
             for ev in lines[xplane.MODULES_LINE].events),
            key=lambda m: m[1])
      starts = [m[1] for m in modules]
      cache: dict[tuple, tuple[str, str]] = {}
      for ev in (lines[xplane.OPS_LINE].events
                 if xplane.OPS_LINE in lines else ()):
        start = float(ev.start_ns)
        i = bisect.bisect_right(starts, start) - 1
        module = modules[i][0] if i >= 0 else ""
        key = (module, ev.name)
        if key not in cache:
          m = _INSTRUCTION.match(ev.name)
          cache[key] = (xplane.ShortName(ev.name), op_names.get(
              module, {}).get(m.group(1) if m else ev.name, ""))
        ops.append([cache[key][0], start, float(ev.duration_ns),
                    cache[key][1]])
  return {"spans": spans, "ops": ops, "modules": modules}


def Save(plain: dict, path: str) -> None:
  with gzip.open(path, "wt") as f:
    json.dump(plain, f, separators=(",", ":"))


def Load(path: str) -> dict:
  if path.endswith(".pb"):
    return LoadPb(path)
  with gzip.open(path, "rt") as f:
    return json.load(f)


@functools.lru_cache(maxsize=1)
def _LoadOnce(path: str) -> dict:
  return LoadPb(path)


def OfRun(run) -> dict:
  """The traced run's spans and ops (loaded once a process)."""
  del run       # run.py hands over no trace path: see TracePath
  return _LoadOnce(TracePath())


# -- idle time by span --------------------------------------------------------


def LeafIntervals(spans) -> dict[int, list[tuple[float, float, str]]]:
  """Per thread, the intervals in which each span was the innermost one
  open: [(start, end, name)], sorted and disjoint. A span that holds others
  keeps what they leave."""
  by_thread: dict[int, list] = {}
  for thread, name, start, dur, *_ in spans:
    by_thread.setdefault(thread, []).append((start, start + dur, name))
  out = {}
  for thread, evs in by_thread.items():
    evs.sort(key=lambda e: (e[0], -e[1]))
    leaves = []
    stack: list[list] = []     # [end, name, cursor]

    def _Pop():
      end, name, cursor = stack.pop()
      if end > cursor:
        leaves.append((cursor, end, name))
      if stack:
        stack[-1][2] = max(stack[-1][2], end)

    for s, e, name in evs:
      while stack and s >= stack[-1][0]:
        _Pop()
      if stack:
        if s > stack[-1][2]:
          leaves.append((stack[-1][2], s, stack[-1][1]))
        stack[-1][2] = max(stack[-1][2], s)
        e = min(e, stack[-1][0])
      stack.append([e, name, s])
    while stack:
      _Pop()
    out[thread] = sorted(leaves)
  return out


def IdleBySpan(busy, w0: float, w1: float, spans) -> dict[str, float]:
  """The idle time of [w0, w1] (what `busy`, sorted disjoint intervals,
  leaves) by the span it passed under, in the units of the inputs. An
  instant goes to the innermost span open on each thread, in equal parts
  where several threads have one open, and to UNSPANNED where none has: the
  values add up to the idle time."""
  import numpy as np
  edges = [w0]
  for s, e in busy:
    s, e = max(s, w0), min(e, w1)
    if e > s:
      edges += [s, e]
  edges.append(w1)
  gs, ge = np.asarray(edges[0::2], float), np.asarray(edges[1::2], float)
  keep = ge > gs
  gs, ge = gs[keep], ge[keep]
  cum = np.concatenate([[0.0], np.cumsum(ge - gs)])

  def _IdleUpTo(t):
    i = np.searchsorted(gs, t, side="right") - 1
    if i < 0:
      return 0.0
    return float(cum[i] + min(max(t - gs[i], 0.0), ge[i] - gs[i]))

  leaves = LeafIntervals(spans)
  cuts = {w0, w1}
  for ivs in leaves.values():
    for s, e, _ in ivs:
      if e > w0 and s < w1:
        cuts.update((max(s, w0), min(e, w1)))
  cuts = sorted(cuts)
  starts = {t: np.asarray([iv[0] for iv in ivs]) for t, ivs in leaves.items()}
  out: dict[str, float] = {}
  for a, b in zip(cuts, cuts[1:]):
    idle = _IdleUpTo(b) - _IdleUpTo(a)
    if idle <= 0:
      continue
    mid = 0.5 * (a + b)
    over = []
    for t, ivs in leaves.items():
      i = int(np.searchsorted(starts[t], mid, side="right")) - 1
      if i >= 0 and ivs[i][0] <= mid < ivs[i][1]:
        over.append(ivs[i][2])
    for name in over or [UNSPANNED]:
      out[name] = out.get(name, 0.0) + idle / max(len(over), 1)
  return out


def _FirstDeviceBusy(ops, w0, w1):
  return xplane.Union([(max(s, w0), min(s + d, w1)) for _, s, d, *_ in ops
                       if s + d > w0 and s < w1])


def IdleUnspannedShare(run):
  """Percent of the first device's idle time, over the traced steps, that
  passed under no `lingvo/` span of any thread. Prints idle seconds by span
  as note idle_by_span. None where the trace holds no such span."""
  w0, w1 = run["trace_step"]["window"]
  plain = OfRun(run)
  if not plain["spans"]:
    return None
  by_span = IdleBySpan(_FirstDeviceBusy(plain["ops"], w0, w1), w0, w1,
                       plain["spans"])
  total = sum(by_span.values())
  open_s: dict[str, float] = {}     # how long each span was the innermost
  for ivs in LeafIntervals(plain["spans"]).values():
    for s, e, name in ivs:
      d = min(e, w1) - max(s, w0)
      if d > 0:
        open_s[name] = open_s.get(name, 0.0) + d
  print(json.dumps({"note": "idle_by_span", "value": {
      "idle_s": total * 1e-9, "window_s": (w1 - w0) * 1e-9,
      "by_span_s": {k: v * 1e-9 for k, v in sorted(
          by_span.items(), key=lambda kv: -kv[1])},
      "span_open_s": {k: v * 1e-9 for k, v in sorted(
          open_s.items(), key=lambda kv: -kv[1])}}}), flush=True)
  if total <= 0:
    return 0.0
  return 100.0 * by_span.get(UNSPANNED, 0.0) / total


# -- device time by scope -----------------------------------------------------


def ScopeOf(op_name: str) -> str:
  """The innermost scope name in an op_name ('jit(f)/while/body/atten/
  kv_write/scatter' -> kv_write; 'transpose(jvp(ffn))/dot_general' -> ffn),
  UNSCOPED where it holds none."""
  found = _SCOPE.findall(op_name or "")
  return found[-1] if found else UNSCOPED


def TimeByScope(ops, w0: float, w1: float, top: int = 10):
  """({scope: self time}, [[short name, self time, scope], ...] of the `top`
  ops) of one device's ops inside [w0, w1]."""
  inside = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
            for n, s, d, *_ in ops if s + d > w0 and s < w1]
  scope_of = {n: ScopeOf(on) for n, on in {(o[0], o[3]) for o in ops}}
  by_scope: dict[str, float] = {}
  by_op: dict[str, float] = {}
  for name, _, self_d in xplane.SelfTimes(inside):
    by_scope[scope_of[name]] = by_scope.get(scope_of[name], 0.0) + self_d
    by_op[name] = by_op.get(name, 0.0) + self_d
  largest = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
  return by_scope, [[n, t, scope_of[n]] for n, t in largest]


def BusyUnscopedShare(run):
  """Percent of the first device's op self time, over the traced steps, in
  ops whose op_name holds none of SCOPES. Prints self seconds by scope as
  note device_time_by_scope (and the ten largest ops with theirs). None
  where no op carries a scope name."""
  w0, w1 = run["trace_step"]["window"]
  by_scope, largest = TimeByScope(OfRun(run)["ops"], w0, w1)
  if not set(by_scope) - {UNSCOPED}:
    return None
  total = sum(by_scope.values())
  print(json.dumps({"note": "device_time_by_scope", "value": {
      "self_s": total * 1e-9,
      "by_scope_s": {k: v * 1e-9 for k, v in sorted(
          by_scope.items(), key=lambda kv: -kv[1])},
      "largest_ops": [[xplane.SafeName(n), t * 1e-9, sc]
                      for n, t, sc in largest]}}), flush=True)
  return 100.0 * by_scope.get(UNSCOPED, 0.0) / total


# -- the engine's step records ------------------------------------------------


def StepRecords(run):
  """The step records that complete inside the run's window, oldest first,
  from the live recorder that holds the most of them. None where the
  program keeps no step records."""
  try:
    from lingvo_tpu.observe import trace as trace_lib
    live = trace_lib.Live()
  except (ImportError, AttributeError):
    return None
  t0, t1 = run["window"]
  best: list = []
  for rec in live:
    steps = [s for s in rec.Steps() if t0 <= s.end_ts <= t1]
    if len(steps) > len(best):
      best = steps
  return best or None


def StepsFromSpans(spans) -> list:
  """The step records a traced stretch's `lingvo/serve/` spans amount to (the
  plain form's "spans"; times in ns become seconds): one StepTrace per
  `.../step` span, its segments the phase spans inside it in the order of
  observe.trace.STEP_SEGMENTS, `loop` the gap since the step before. For a
  recorded trace read without the process that made it."""
  from lingvo_tpu.observe import trace as trace_lib
  segments = trace_lib.STEP_SEGMENTS
  prefix = SPAN_PREFIX + "serve/"
  evs = sorted((s, s + d, n[len(prefix):], a) for _, n, s, d, a in spans
               if n.startswith(prefix))
  out, last_end = [], None
  for s0, e0, name, args in evs:
    if name != "step":
      continue
    acc, i = [0.0] * len(segments), 0
    for s, e, child, _ in evs:
      if child != "step" and s >= s0 and e <= e0 and child in segments[i:]:
        i = segments.index(child, i)
        acc[i] += (e - s) * 1e-9
    out.append(trace_lib.StepTrace(
        int(args.get("step", len(out))), s0 * 1e-9,
        (s0 - last_end) * 1e-9 if last_end is not None else 0.0, tuple(acc),
        int(args.get("valid_tokens", 0)), int(args.get("prefill_tokens", 0)),
        int(args.get("rows", 0))))
    last_end = e0
  return out


def HostGaps(steps) -> list[tuple[float, float]]:
  """(host seconds, period seconds) for each pair of consecutive steps:
  from step n's results arriving (its device_wait over) to step n + 1's
  launch (its dispatch returned), and from the one's end to the other's."""
  out = []
  for a, b in zip(steps, steps[1:]):
    if b.step != a.step + 1:
      continue
    host = (sum(a.segments_s[_DEVICE_WAIT + 1:]) + b.loop_s
            + sum(b.segments_s[:_DISPATCH + 1]))
    out.append((host, b.end_ts - a.end_ts))
  return out


def StepSpanMs(run):
  steps = StepRecords(run)
  if steps is None:
    return None
  return 1e3 * statistics.median(s.span_s for s in steps)


def _PhaseTable(steps) -> dict:
  names = list(steps[0].Phases()) + ["loop"]
  cols = {n: [] for n in names}
  for s in steps:
    for n, v in s.Phases().items():
      cols[n].append(v * 1e3)
    cols["loop"].append(s.loop_s * 1e3)
  return {n: {"p50": readings.Percentile(v, 50),
              "p95": readings.Percentile(v, 95)} for n, v in cols.items()}


def _TraceCost(run, steps) -> dict:
  """The same run's steps before and during its traced tail (a traced run
  traces the window's last seconds): what tracing costs when it is on."""
  cut = run.get("trace_from") or run["window"][1] - _TRACE_TAIL_S
  out = {}
  for key, part in (("before", [s for s in steps if s.end_ts < cut]),
                    ("during", [s for s in steps if s.start_ts >= cut])):
    gaps = HostGaps(part)
    if part and gaps:
      out[key] = {
          "steps": len(part),
          "step_span_ms": 1e3 * statistics.median(s.span_s for s in part),
          "step_host_ms": 1e3 * statistics.median(h for h, _ in gaps),
          "period_ms": 1e3 * statistics.median(p for _, p in gaps)}
  return out


def StepHostMs(run):
  """Median host time between one step's results and the next one's launch.
  Prints the per-phase table as note step_host_phases_ms, and the steps
  before and during the traced tail as note step_trace_cost."""
  steps = StepRecords(run)
  if steps is None:
    return None
  gaps = HostGaps(steps)
  if not gaps:
    return None
  print(json.dumps({"note": "step_host_phases_ms", "value": dict(
      _PhaseTable(steps), steps=len(steps),
      span_over_phases=statistics.median(
          s.span_s / max(sum(s.Phases().values()), 1e-12)
          for s in steps))}), flush=True)
  print(json.dumps({"note": "step_trace_cost",
                    "value": _TraceCost(run, steps)}), flush=True)
  return 1e3 * statistics.median(h for h, _ in gaps)


def StepH2dMs(run):
  """Median `h2d` phase of the window's steps: the host placing the step's
  arguments on the device, while the device waits for them."""
  steps = StepRecords(run)
  if steps is None:
    return None
  return 1e3 * statistics.median(s.Phases()["h2d"] for s in steps)


def Periods(steps) -> list[tuple]:
  """(step record, period seconds) for each step whose predecessor is also on
  record: from the one's end to the other's. A period is the step's span
  plus the loop's turn-around before it, so the periods tile the window."""
  return [(b, b.end_ts - a.end_ts) for a, b in zip(steps, steps[1:])
          if b.step == a.step + 1]


def WindowReport(steps, t0: float, t1: float, factor: float = _STALL_FACTOR,
                 keep: int = _STALLS_KEPT) -> dict | None:
  """Where a window's seconds went, from its step records alone: the steps,
  the median period and their product beside the window's length (what the
  product leaves is time in which no step of the usual length completed),
  every period over `factor` times the median with its time in the window,
  its length and its seconds per phase (the `keep` longest, in order of
  time), their sum and their sum's excess over the median, the periods
  between 1.25 times the median and `factor` times counted the same way, the
  phases' p50 and p95, and the median period and `h2d` by sixth of the
  window (a process that changes its mode inside a run shows there)."""
  pairs = Periods(steps)
  if not pairs:
    return None
  periods = [p for _, p in pairs]
  median = statistics.median(periods)
  long_ = [(s, p) for s, p in pairs if p > factor * median]
  slow = [p for p in periods if 1.25 * median < p <= factor * median]

  def _Row(s, p):
    phases = {k: round(v, 5) for k, v in s.Phases().items()}
    phases["loop"] = round(s.loop_s, 5)
    return {"at_s": round(s.end_ts - p - t0, 3), "period_s": round(p, 4),
            "step": s.step, "rows": s.rows, "prefill_tokens": s.prefill_tokens,
            "phases_s": phases}

  sixths = [[] for _ in range(6)]
  for s, p in pairs:
    k = max(0, min(5, int(6 * (s.end_ts - t0) / (t1 - t0))))
    sixths[k].append((p, s.Phases()["h2d"]))
  kept = sorted(sorted(long_, key=lambda sp: -sp[1])[:keep],
                key=lambda sp: sp[0].end_ts)
  return {
      "steps": len(steps), "period_ms_median": 1e3 * median,
      "steps_x_median_s": len(steps) * median, "window_s": t1 - t0,
      "periods_s": sum(periods),
      "stall_factor": factor, "stalls": len(long_),
      "stall_periods_s": sum(p for _, p in long_),
      "stall_excess_s": sum(p - median for _, p in long_),
      "slow_periods": len(slow),
      "slow_excess_s": sum(p - median for p in slow),
      "stalled_steps": [_Row(s, p) for s, p in kept],
      "phases_ms": {k: {q: round(v, 3) for q, v in d.items()}
                    for k, d in _PhaseTable(steps).items()},
      "period_ms_p50_by_sixth": [
          round(1e3 * statistics.median(p for p, _ in x), 2) if x else None
          for x in sixths],
      "h2d_ms_p50_by_sixth": [
          round(1e3 * statistics.median(h for _, h in x), 3) if x else None
          for x in sixths]}


def StepStallShare(run):
  """Percent of the window's summed step periods that lies in periods over
  twice the median period: seconds in which the engine was held up, by the
  host, the lock or the device. Every serving run, traced or not, prints
  the same window's report as note step_stalls (serve_cell.Run)."""
  steps = StepRecords(run)
  if steps is None:
    return None
  report = WindowReport(steps, *run["window"])
  if report is None:
    return None
  return 100.0 * report["stall_periods_s"] / report["periods_s"]


def StepHostShare(run):
  """The host time between steps summed over the window, over the summed
  step periods: the host's estimate of the device's idle share."""
  steps = StepRecords(run)
  if steps is None:
    return None
  gaps = HostGaps(steps)
  if not gaps:
    return None
  return 100.0 * sum(h for h, _ in gaps) / sum(p for _, p in gaps)


# -- the train loop -----------------------------------------------------------


def TrainHostMs(run):
  """Median over the window's loops of host_overhead_s - infeed_wait_s: what
  the main thread spends placing and dispatching one loop. Prints the loops
  before and during the traced tail (a traced run traces the window's last
  loops, six seconds or two of them) as note train_trace_cost."""
  host = [1e3 * (r["host_overhead_s"] - r["infeed_wait_s"])
          for r in run["loop_results"]
          if "host_overhead_s" in r and "infeed_wait_s" in r]
  if not host:
    return None
  intervals = run["intervals"]
  traced = min(len(intervals), max(2, math.ceil(
      _TRACE_TAIL_S / statistics.median(intervals))))
  cost = {}
  for key, part_i, part_h in (
      ("before", intervals[:-traced], host[:-traced]),
      ("during", intervals[-traced:], host[-traced:])):
    if part_i and part_h:
      cost[key] = {"loops": len(part_i),
                   "loop_interval_ms": 1e3 * statistics.median(part_i),
                   "train_host_ms": statistics.median(part_h)}
  print(json.dumps({"note": "train_trace_cost", "value": cost}), flush=True)
  return statistics.median(host)
