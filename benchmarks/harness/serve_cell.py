"""A serving cell: ServingLoop started as a user starts it, driven from one
client thread in the engine's process (a chip belongs to one process) that
submits requests when they are due, stamps every streamed token on its own
clock, and closes the window."""

from __future__ import annotations

import gc
import importlib
import threading
import time

import numpy as np

from benchmarks.harness import model as model_lib
from benchmarks.harness import readings
from benchmarks.harness import spans
from benchmarks.harness import traffic as traffic_lib

_OK_REASONS = (None, "length", "eos")
_CLIENT_POLL_S = 1e-3     # how often the client looks at its streams
_TRACE_TAIL_S = 6.0       # a traced run traces the window's last seconds
_PROBE_PATIENCE_S = 900.0  # the probe's first call in a checkout compiles
_LONG_PASS_S = 0.020      # a client pass or a collection worth a note
_NOTED = 200              # such entries a note lists at most
_OPEN_PATIENCE_S = 45.0   # a window that the work opens has opened by then,
_WORK_PATIENCE = 2.0      # and its work is done within `seconds` x this


class _Stream:
  """One request as the client sees it."""
  __slots__ = ("req", "due", "sent", "handle", "seen", "stamps", "done_at",
               "error")

  def __init__(self, req):
    self.req = req
    self.due = None
    self.sent = None
    self.handle = None
    self.seen = 0
    self.stamps: list[float] = []
    self.done_at = None
    self.error = None


class StepRecorder:
  """Spans around the engine's StepOnce, from the benchmark's side: after
  every step the completion time, the step's duration, the engine's token
  counters, and (tokens this step, context length) of every live row, which
  is what the ragged kernel's required operations and bytes follow from."""

  def __init__(self, engine):
    self.engine = engine
    # (t_end, dur, steps, tokens_done, requests finished so far)
    self.records: list[tuple] = []
    self.rows: list[list[tuple[int, int]]] = []
    self.attend: list[tuple[int, int]] = []   # (query blocks, queries) so far
    reg = engine.metrics
    self._counters = [reg.Counter("serving/" + k) for k in
                      ("steps", "prompt_tokens", "prefix_hit_tokens",
                       "tokens_emitted", "attend_query_blocks",
                       "attend_block_queries")]
    self._inner = engine.StepOnce
    engine.StepOnce = self._StepOnce

  def _StepOnce(self):
    live_before = {s.id: s.pos for s in self.engine.sched.slots
                   if s is not None}
    t0 = time.perf_counter()
    n = self._inner()
    t1 = time.perf_counter()
    steps, prompt, shared, emitted, blocks, queries = (
        c.value for c in self._counters)
    if not self.records or steps != self.records[-1][2]:
      rows = []
      after = set()
      for s in self.engine.sched.slots:
        if s is not None:
          after.add(s.id)
          rows.append((s.pos - live_before.get(s.id, s.reused_tokens), s.pos))
      # a sequence that finished in this step left its slot: its last token
      rows.extend((1, p + 1) for i, p in live_before.items() if i not in after)
      self.records.append((t1, t1 - t0, steps, prompt + shared + emitted,
                           self.engine.sched.finished))
      self.rows.append(rows)
      self.attend.append((blocks, queries))
    return n

  def Detach(self):
    self.engine.StepOnce = self._inner


class LogitProbe:
  """One engine step, after the window, through a program of the benchmark's
  own: the task's ragged step as the engine calls it (same weights, same
  paged cache as the traffic left it, same packed rows and block tables),
  returning the step's logits beside the greedy tokens and the new cache.
  The engine goes on from it as from any step. The engine keeps its logits
  to itself, so this is where `correct` gets numbers to compare; it hangs on
  the engine's compile log, through which every step program is called."""

  def __init__(self, engine, task):
    import jax
    import jax.numpy as jnp
    if engine.temperature > 0 or engine.spec is not None:
      raise ValueError("the probe stands in for the greedy, draft-less step")
    self.engine = engine
    donate = (1,) if jax.default_backend() != "cpu" else ()

    def _Step(theta, states, tok_ids, rows, tables):
      logits, new_states = task.RaggedStep(theta, tok_ids[None], states,
                                           tables, rows)
      logits = logits[0]                                     # [T, V]
      return logits, jnp.argmax(logits, -1).astype(jnp.int32), new_states

    self._fn = jax.jit(_Step, donate_argnums=donate)
    self._inner = engine._compile_log.Call
    engine._compile_log.Call = self._Call
    self.armed = threading.Event()
    self.done = threading.Event()
    self.captured = None

  def _Call(self, name, fn, *args):
    if name != "ragged" or not self.armed.is_set() or self.done.is_set():
      return self._inner(name, fn, *args)
    theta, states, tok_ids, rows, tables = args[:5]
    slot_ids = [None if s is None else s.id for s in self.engine.sched.slots]
    logits, sampled, new_states = self._fn(theta, states, tok_ids, rows,
                                           tables)
    self.captured = {
        "logits": logits, "slot_ids": slot_ids,
        "tok_ids": np.asarray(tok_ids), "row_of": np.asarray(rows.row_of),
        "pos": np.asarray(rows.pos), "valid": np.asarray(rows.valid)}
    self.done.set()
    return sampled, new_states

  def Detach(self):
    self.engine._compile_log.Call = self._inner


class GcWatch:
  """The interpreter's garbage collections while it is open (gc.callbacks):
  count and seconds by generation, and each one of _LONG_PASS_S or more with
  its time. A collection holds the interpreter, so it stops the engine's
  thread and the client's alike."""

  def __init__(self, clock=time.perf_counter):
    self._clock = clock
    self.by_generation: dict[int, list] = {}    # generation: [count, seconds]
    self.long: list[tuple[float, int, float]] = []   # (start, generation, s)
    self._t0 = None

  def _On(self, phase, info):
    now = self._clock()
    if phase == "start":
      self._t0 = now
    elif self._t0 is not None:
      tot = self.by_generation.setdefault(info["generation"], [0, 0.0])
      tot[0] += 1
      tot[1] += now - self._t0
      if now - self._t0 >= _LONG_PASS_S and len(self.long) < _NOTED:
        self.long.append((self._t0, info["generation"], now - self._t0))
      self._t0 = None

  def __enter__(self):
    gc.callbacks.append(self._On)
    return self

  def __exit__(self, *exc):
    gc.callbacks.remove(self._On)


def _WindowNotes(ctx, t0, t1, steps, gc_watch, client_gaps):
  """What every run says about where its window went (tracing on or off):
  the engine's own step records reduced to note step_stalls, and beside them
  what the other thread and the interpreter were doing at those times: the
  client's long passes with the CPU time that went into them (none, in the
  thread and in the whole process: the machine stood still), the long
  collections, the compile events. Times are seconds from the window's
  start; the lead-in is negative."""
  records = spans.StepRecords({"window": (t0, t1)})
  if records is not None:
    ctx.Note("step_stalls", spans.WindowReport(records, t0, t1))
    # every step of the window, for whoever reads the run afterwards:
    # [step, start_s, loop_s, rows, prefill_tokens, *segments_s]
    ctx.Note("step_records", [
        [s.step, round(s.start_ts - t0, 6), round(s.loop_s, 6), s.rows,
         s.prefill_tokens, *(round(x, 6) for x in s.segments_s)]
        for s in records], quiet=True)
  longs = client_gaps["long"]
  ctx.Note("client_gaps", {
      "long_pass_ms": 1e3 * _LONG_PASS_S,
      "passes": client_gaps["passes"], "long": len(longs),
      "long_s": sum(x[1] for x in longs),
      # a pass in which neither this thread nor any other of the process
      # used the CPU was the process standing still, not the interpreter
      "at_s_ms_thread_cpu_ms_process_cpu_ms": [
          [round(t - t0, 3), round(1e3 * d, 1), round(1e3 * th, 1),
           round(1e3 * pr, 1)] for t, d, th, pr in longs[:_NOTED]]})
  # the window on the machine's monotonic clock, which every process shares
  ctx.Note("window_perf_counter", [t0, t1])
  ctx.Note("gc", {
      "by_generation": {str(g): {"collections": n, "seconds": round(sec, 4)}
                        for g, (n, sec) in sorted(
                            gc_watch.by_generation.items())},
      "long_at_s_generation_ms": [[round(t - t0, 3), g, round(1e3 * d, 1)]
                                  for t, g, d in gc_watch.long]})
  ctx.Note("compiles_in_window", [
      [round(t - t0, 3), name, round(sec, 4)]
      for t, name, sec in ctx.compile_clock.events
      if t0 <= t <= t1])
  # tokens per second in each second since the clients' start: the start's
  # wave of prefill and where it ends, which the window has to open behind
  t_gen0 = ctx.t_gen0
  ctx.Note("tok_s_by_second_from_start", _BySlice(
      [x[:2] for x in steps], t_gen0, t_gen0 + int(t1 - t_gen0),
      int(t1 - t_gen0)))
  # every step completion since then with the tokens done and the requests
  # finished so far: what the window would have read behind any other edges
  # (readings.FinishWindow, TokenWindowRate; benchmarks/tools/window_offline.py)
  ctx.Note("step_completions_from_start", [
      [round(t - t_gen0, 4), n, f] for t, n, f in steps if t >= t_gen0],
           quiet=True)


def Run(ctx) -> dict:
  import jax
  import jax.numpy as jnp
  from lingvo_tpu.serving import engine as engine_lib

  cell, traffic = ctx.cell, ctx.cell["traffic"]
  sizes = model_lib.Sizes(cell["config"], ctx.rehearse)
  geo = sizes["serving"]
  mp = model_lib.ModelParams(sizes, num_layers=sizes["num_layers"],
                             flash=False, remat_policy=None,
                             input_seed=ctx.seed)
  task = model_lib.Instantiate(mp.task)

  reference = importlib.import_module(
      "benchmarks.references." + cell["config"]["reference"])

  def _Init(key):
    # served weights live in the fprop dtype, made on the device
    theta = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        task.InstantiateVariables(key))
    return reference.SeededWeights(theta, **cell["config"]["weights"])

  theta = jax.jit(_Init)(jax.random.PRNGKey(ctx.seed % (2**31)))
  engine = engine_lib.ServingLoop(
      task, theta, page_size=geo["page_size"], num_pages=geo["num_pages"],
      max_batch=geo["max_batch"], max_seq_len=geo["max_seq_len"],
      prefill_token_budget=geo["prefill_token_budget"])
  want_path = "xla" if ctx.rehearse else "pallas"
  if engine.paged_path != want_path:
    raise RuntimeError(f"paged_path {engine.paged_path!r}, want {want_path!r}")
  # the roofline readers count with the file's KV heads and head size: they
  # are the program's only if the engine's own page pools (the leaves the
  # program's layout says are pools) end in the rows the file states
  ctx.Note("read_back", model_lib.ReadBackPools(sizes, task, theta,
                                                engine._states))
  packed_t = geo["max_batch"] + geo["prefill_token_budget"]

  scale = float(sizes.get("length_scale", 1.0)) if ctx.rehearse else 1.0
  tr = _Scaled(traffic, scale)
  requests = traffic_lib.Generate(tr, ctx.seconds, ctx.seed, geo["max_batch"])
  ctx.Note("offered", traffic_lib.TotalWork(requests))
  vocab = sizes["vocab_size"]
  prompts = {r.index: traffic_lib.PromptIds(r, ctx.seed, vocab)
             for r in requests}

  died = []
  prev_hook = threading.excepthook
  threading.excepthook = lambda a: (died.append(a), prev_hook(a))
  recorder = StepRecorder(engine)
  probe = LogitProbe(engine, task)
  engine.Start()
  try:
    # warm-up: the one packed step program, with a prefill that spans
    # chunks and a few decode steps; counted as set-up
    warm = [engine.Submit(
        np.arange(1, 1 + min(geo["prefill_token_budget"] + 9,
                             geo["max_seq_len"] // 2), dtype=np.int32) % vocab
        + 1, 4) for _ in range(2)]
    while not all(h.done for h in warm):
      if died:
        raise RuntimeError(f"serving loop died: {died[0].exc_value!r}")
      time.sleep(0.005)
    n_warm_steps = len(recorder.records)
    finished_warm = engine.sched.finished
    ctx.SetupEnds(time.perf_counter())
    ctx.Note("ready_s", ctx.t_setup_end - ctx.t_process)

    with GcWatch() as gc_watch:
      streams, client_gaps = _Drive(ctx, engine, tr, requests, prompts,
                                    died, geo)
    if ctx.trace and ctx.trace_started:
      jax.profiler.stop_trace()
      ctx.trace_started = False
    t_probe = time.perf_counter()
    extra = _ProbeOneStep(probe, engine, streams, prompts, died)
    ctx.Note("probe_wait_s", round(time.perf_counter() - t_probe, 2))
  finally:
    engine.Stop(drain=False)
    recorder.Detach()
    probe.Detach()
    threading.excepthook = prev_hook
    if ctx.trace and ctx.trace_started:
      jax.profiler.stop_trace()

  steps = [(t, n, f - finished_warm)
           for t, _, _, n, f in recorder.records[n_warm_steps:]]
  token_steps = [x[:2] for x in steps]
  closed_loop = tr["loop"] == "closed"
  if closed_loop:
    # the work defines the edges; the client only kept the run going until
    # it saw the window's last request finish
    win = readings.FinishWindow(
        [x for x in steps if x[0] >= ctx.t_gen0], _OpeningFinish(tr, geo),
        traffic_lib.WindowRequests(tr, ctx.seconds))
    ctx.t_win0, ctx.t_win1 = win["t_open"], win["t_close"]
    rate = win["tok_s"]
    ctx.Note("serve_tok_s_between_finishes", dict(
        win, t_open=win["t_open"] - ctx.t_gen0,
        t_close=win["t_close"] - ctx.t_gen0))
  else:
    rate, tokens, span = readings.TokenWindowRate(token_steps, ctx.t_win0,
                                                  ctx.t_win1)
    ctx.Note("serve_tok_s_between_steps", {"tok_s": rate, "tokens": tokens,
                                           "seconds": span})
  t0, t1 = ctx.t_win0, ctx.t_win1
  _WindowNotes(ctx, t0, t1, steps, gc_watch, client_gaps)
  sampled = [s for s in streams if s.req.sampled]
  gaps = []
  for s in streams:
    for a, b in zip(s.stamps, s.stamps[1:]):
      if t0 <= b <= t1:
        gaps.append((b - a) * 1e3)
  ttft = []
  for s in sampled:
    if s.sent is None:
      continue
    first = s.stamps[0] if s.stamps else t1     # censored at the window's end
    ttft.append((first - s.due) * 1e3)
  late = [(s.sent - s.due) * 1e3 for s in streams if s.sent is not None
          and s.due is not None]
  if closed_loop:
    # the requests the engine finished between the window's two steps, by
    # their rank among the finishes as the client saw them (it looks at its
    # streams many times a step, so ranks agree though stamps lag)
    done = sorted((s for s in streams if s.done_at is not None),
                  key=lambda s: s.done_at)
    first = win["finished_at_open"]
    done_in = done[first:first + win["finished"]]
  else:
    done_in = [s for s in streams if s.done_at is not None
               and t0 < s.done_at <= t1]
  finished_tok_s = sum(
      s.req.prompt_len + len(s.stamps) for s in done_in) / (t1 - t0)
  failed = sum(1 for s in streams if s.error is not None or (
      s.handle is not None and s.handle.finish_reason not in _OK_REASONS
      and s.done_at is not None and s.done_at <= t1))
  attempted = sum(1 for s in streams if s.sent is not None or s.error)
  ctx.Note("client", {"streams": len(streams), "sampled": len(sampled),
                      "itl_gaps": len(gaps), "ttft_samples": len(ttft),
                      "finished_in_window": len(done_in)})

  in_win = [(t, d) for t, d, *_ in recorder.records if t0 <= t <= t1]
  # queue wait by thirds of the window: growing thirds mean a growing backlog
  thirds = [[], [], []]
  for s in sampled:
    if s.sent is not None and s.handle is not None:
      admitted = s.handle.admit_time if s.handle.admit_time is not None else t1
      k = max(0, min(2, int(3 * (s.due - t0) / (t1 - t0))))
      thirds[k].append((admitted - s.handle.submit_time) * 1e3)
  ctx.Note("latency_summary", {
      "queue_wait_ms_median_by_third": [
          round(readings.Percentile(x, 50), 2) if x else None for x in thirds],
      "ttft_ms_p50_p95": [round(readings.Percentile(ttft, q), 2)
                          for q in (50, 95)] if ttft else None,
      "itl_ms_p50_p95_p99": [round(readings.Percentile(gaps, q), 2)
                             for q in (50, 95, 99)] if gaps else None,
      "gen_late_ms_p99": round(readings.Percentile(late, 99), 3) if late
      else None,
      "step_ms_median": round(readings.Percentile(
          [d * 1e3 for _, d in in_win], 50), 2) if in_win else None,
      "steps_in_window": len(in_win),
      "tok_s_by_sixth": _BySlice(token_steps, t0, t1, 6),
      "open_at_end": sum(1 for s in streams if s.sent is not None
                         and s.done_at is None),
      "finished_tok_s": finished_tok_s})
  stats = engine.Stats()
  end_to_end = {"serve_tok_s": rate}
  if gaps:
    end_to_end["itl_p95_ms"] = readings.Percentile(gaps, 95)
  admit_wait = [(s.handle.admit_time - s.handle.submit_time) * 1e3
                for s in sampled if s.handle is not None
                and s.handle.admit_time is not None]
  run = {
      "chips": 1, "sizes": sizes, "packed_t": packed_t,
      "window": (t0, t1), "trace_from": ctx.t_trace0,
      "step_records": recorder.records[n_warm_steps:],
      "step_rows": recorder.rows[n_warm_steps:],
      "attend_blocks": recorder.attend[n_warm_steps:],
      "attend_bq": getattr(engine, "_attend_bq", 0),
      "step_durations_ms": [d * 1e3 for _, d in in_win],
      "window_steps": len(in_win),
      "itl_gaps_ms": gaps, "ttft_ms": ttft, "gen_late_ms": late,
      "queue_wait_ms": admit_wait, "finished_tok_s": finished_tok_s,
      "kv_pages": stats["kv_pages"], "scheduler": stats["scheduler"],
      **end_to_end,
  }
  by_id = {s.handle.id: (prompts[s.req.index], s.handle)
           for s in streams + extra if s.handle is not None}
  correct, detail = _Correct(ctx, reference, theta, sizes, by_id,
                             probe.captured)
  ctx.Note("correct_detail", detail)
  compared = {}
  if "max_abs_diff" in detail:
    compared["logit_max_abs_diff"] = {"value": detail["max_abs_diff"],
                                      "limit": detail["tolerance"]}
  return {"run": run, "end_to_end": end_to_end, "correct": correct,
          "attempted": attempted, "failed": failed, "compared": compared}


def _BySlice(steps, t0, t1, k):
  """Token rate in each k-th of the window (from the step completions that
  bracket it): shows whether the window holds one phase or a steady mix."""
  out = []
  for i in range(k):
    a, b = t0 + (t1 - t0) * i / k, t0 + (t1 - t0) * (i + 1) / k
    try:
      out.append(round(readings.TokenWindowRate(steps, a, b)[0], 1))
    except ValueError:
      out.append(None)
  return out


def _Scaled(traffic: dict, scale: float) -> dict:
  """The rehearsal's tiny engine takes the same mix with lengths scaled and
  a lead-in of two seconds at most."""
  if scale == 1.0:
    return traffic
  out = dict(traffic)
  if "lead_in_s" in traffic:
    out["lead_in_s"] = min(2.0, traffic["lead_in_s"])
  for k in ("prompt_len", "new_tokens"):
    d = dict(traffic[k])
    for f in ("median", "min", "max", "value"):
      if f in d:
        d[f] = max(1, round(d[f] * scale))
    out[k] = d
  return out


def _Drive(ctx, engine, tr, requests, prompts, died, geo):
  """The client: one loop on this thread. Open loop: submits each request
  when it is due, timed from then. Closed loop: each of the clients sends
  its next request when its last one finished. Every millisecond
  (`_CLIENT_POLL_S`) it looks at each open stream and stamps the tokens that
  arrived. Returns the
  streams and its own long passes (a pass is a millisecond's sleep and a
  look at 64 streams: one of 20 ms or more means this thread was kept from
  running), to lay beside the engine's long steps."""
  import jax
  poll = _CLIENT_POLL_S
  open_loop = tr["loop"] == "open"
  streams = [_Stream(r) for r in requests]
  pending = list(streams)
  live: list[_Stream] = []
  cycle = 0
  # this loop's passes over _LONG_PASS_S: (start, seconds, this thread's CPU
  # seconds in it, the whole process's)
  gaps = {"passes": 0, "long": []}
  t_gen0 = ctx.t_gen0 = time.perf_counter()
  # open loop: the window opens `lead_in_s` after the clients' start and
  # lasts `seconds`. Closed loop: it opens when the opening request has finished
  # and closes when `in_window` more have, and by nothing else; a system that
  # takes over `_OPEN_PATIENCE_S` to open it, or over `seconds` x
  # `_WORK_PATIENCE` for its work, fails the run (the traffic file's
  # `requests_per_s_hint` is then far off what the cell turns over)
  opening = in_window = trace_from_done = n_done = 0
  if not open_loop:
    opening = _OpeningFinish(tr, geo)
    in_window = traffic_lib.WindowRequests(tr, ctx.seconds)
    # a traced run traces the window's last seconds: the last requests
    trace_from_done = opening + in_window - round(
        _TRACE_TAIL_S * in_window / ctx.seconds)
    ctx.t_win0 = ctx.t_win1 = None
  else:
    ctx.t_win0 = t_gen0 + float(tr.get("lead_in_s", 0.0))
    ctx.t_win1 = ctx.t_win0 + ctx.seconds
  for s in streams:
    s.due = t_gen0 + s.req.due_s if open_loop else None
  clients = 0 if open_loop else traffic_lib.NumClients(tr, geo["max_batch"])

  def _Send(s, now):
    s.sent = now
    if s.due is None:
      s.due = now
    try:
      s.handle = engine.Submit(prompts[s.req.index], s.req.new_tokens)
      live.append(s)
    except Exception as e:  # noqa: BLE001 - a refused request is a failed one
      s.error = repr(e)

  last, last_cpu = t_gen0, (time.thread_time(), time.process_time())
  while True:
    now = time.perf_counter()
    cpu = (time.thread_time(), time.process_time())
    gaps["passes"] += 1
    if now - last > _LONG_PASS_S:
      gaps["long"].append((last, now - last, cpu[0] - last_cpu[0],
                           cpu[1] - last_cpu[1]))
    last, last_cpu = now, cpu
    if ctx.t_win1 is None:
      if n_done >= opening:
        ctx.t_win0 = now
        ctx.t_win1 = now + ctx.seconds * _WORK_PATIENCE
      elif now > t_gen0 + _OPEN_PATIENCE_S:
        raise RuntimeError(f"{n_done} of the {opening} requests that open "
                           f"the window finished in {_OPEN_PATIENCE_S} s")
    elif opening and n_done >= opening + in_window:
      break
    elif now >= ctx.t_win1:
      if open_loop:
        break
      raise RuntimeError(
          f"{n_done - opening} of the window's {in_window} requests finished "
          f"in {ctx.seconds * _WORK_PATIENCE} s: no reading")
    if died:
      raise RuntimeError(f"serving loop died: {died[0].exc_value!r}")
    if ctx.trace and not ctx.trace_started and ctx.t_win1 is not None and (
        now >= ctx.t_win1 - _TRACE_TAIL_S
        or (opening and n_done >= trace_from_done)):
      ctx.t_trace0 = now
      jax.profiler.start_trace(ctx.trace_dir)
      ctx.trace_started = True
    if open_loop:
      while pending and pending[0].due <= now:
        _Send(pending.pop(0), now)
    else:
      while len(live) < clients:
        if not pending:        # the system outran the hint: go round again
          cycle += 1
          again = [_Stream(s.req) for s in streams[:len(requests)]]
          streams.extend(again)
          pending.extend(again)
        _Send(pending.pop(0), now)
    still = []
    for s in live:
      n = len(s.handle._tokens)     # what Tokens() would have yielded by now
      if n > s.seen:
        s.stamps.extend([now] * (n - s.seen))
        s.seen = n
      if s.handle.done and s.seen == len(s.handle._tokens):
        s.done_at = now
        n_done += 1
      else:
        still.append(s)
    live[:] = still
    time.sleep(poll)
  ctx.Note("closed_loop_cycles", cycle)
  return streams, gaps


def _OpeningFinish(tr, geo) -> int:
  """A closed loop's window opens when every client has been served once:
  the start's wave of simultaneous admissions is behind it."""
  return traffic_lib.NumClients(tr, geo["max_batch"])


def _ProbeOneStep(probe, engine, streams, prompts, died):
  """After the window: lets the probe take the engine's next step with a
  live row in it. The sequences still open carry on, so their rows read the
  pages the window's traffic wrote. Where none is open (a short rehearsal),
  one of the run's requests is sent again. Returns those extra streams."""
  extra = []
  probe.armed.set()
  t_end = time.perf_counter() + _PROBE_PATIENCE_S
  while not probe.done.wait(0.25):
    if died:
      raise RuntimeError(f"serving loop died: {died[0].exc_value!r}")
    if time.perf_counter() > t_end:
      break
    idle = not any(s is not None for s in engine.sched.slots)
    if idle and all(s.handle.done for s in extra) and len(extra) < 8:
      s = _Stream(streams[-1].req)
      s.handle = engine.Submit(prompts[s.req.index], s.req.new_tokens)
      extra.append(s)
  return extra


def _Correct(ctx, reference, theta, sizes, by_id, captured
             ) -> tuple[bool, dict]:
  """The logits of one engine step (LogitProbe) at the newest position of a
  seeded sample of its live rows, over the whole vocabulary, against the
  plain reference's logits there: an unpaged f32 forward of the same weights
  over the sequence so far (prompt and streamed tokens)."""
  import jax
  import jax.numpy as jnp
  spec = ctx.cell["config"]["correct"]
  if captured is None:
    return False, {"error": "no engine step with a live row after the window"}
  rows = []     # (slot, token indices of the row in the packed step)
  for slot, seq_id in enumerate(captured["slot_ids"]):
    idx = np.flatnonzero((captured["row_of"] == slot) & captured["valid"])
    if seq_id in by_id and len(idx):
      rows.append((slot, idx[np.argsort(captured["pos"][idx])]))
  if not rows:
    return False, {"error": "the probed step held no row of this run"}
  rng = np.random.RandomState(ctx.seed % (2**32))
  pick = [rows[i] for i in sorted(
      rng.permutation(len(rows))[:int(spec["serve_sample_rows"])])]
  lens = [int(captured["pos"][idx[-1]]) + 1 for _, idx in pick]
  # one width for every run, so that the reference compiles once a checkout
  width = sizes["serving"]["max_seq_len"]
  ids = np.zeros((len(pick), width), np.int32)
  for k, (slot, idx) in enumerate(pick):
    prompt, handle = by_id[captured["slot_ids"][slot]]
    seq = np.concatenate([prompt, np.asarray(handle._tokens, np.int32)])
    ids[k, :lens[k]] = seq[:lens[k]]
    fed = captured["tok_ids"][idx]
    if not np.array_equal(fed, ids[k, captured["pos"][idx]]):
      return False, {"error": "the step fed other tokens than the sequence "
                              "holds at those positions", "slot": slot}
  at = np.asarray(lens, np.int32) - 1
  last = np.asarray([int(idx[-1]) for _, idx in pick], np.int32)
  got = captured["logits"][jnp.asarray(last)].astype(jnp.float32)
  # beside each sequence, the same with its first page holding other tokens:
  # how far one wrong page of the cache moves the reference's own logits,
  # on record in every run beside the tolerance it has to exceed
  page = sizes["serving"]["page_size"]
  wrong = ids.copy()
  for k, n in enumerate(lens):
    if n > page:               # else the sequence is its newest page only
      wrong[k, :page] = (wrong[k, :page] + 1) % sizes["vocab_size"]
  t0 = time.perf_counter()
  with jax.default_matmul_precision("highest"):
    both = jax.device_get(jax.jit(lambda th, i, a: reference.LogitsAt(
        th, i, a, sizes.get("logit_cap", 30.0)))(
            theta, jnp.asarray(np.concatenate([ids, wrong])),
            jnp.asarray(np.concatenate([at, at]))))
  want, want_wrong = both[:len(pick)], both[len(pick):]
  ok, detail = readings.CompareLogits(jax.device_get(got), want,
                                      float(spec["serve_logit_tol"]))
  detail["reference_s"] = round(time.perf_counter() - t0, 2)
  detail["one_wrong_page_max_abs_diff"] = [
      round(float(x), 4) for x in np.abs(want_wrong - want).max(-1)]
  detail.update(context_lens=lens,
                tokens_in_step=[len(idx) for _, idx in pick],
                live_rows=len(rows))
  return ok, detail
