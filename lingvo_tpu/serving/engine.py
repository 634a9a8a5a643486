"""ServingLoop: the continuous-batching serving engine driver.

Glues the layers below into a running service:

    core/transformer.py          the stack: the step's forward pass, its counts
    serving/kv_cache.py          host-side page and slot ownership, and the
                                 census of the stack that prices them
    serving/state_layout.py      which leaves of the decode state are pages
                                 or a slot's; the gather / scatter over them
    serving/scheduler.py         admission / step building / retirement

Device-side there is ONE compiled step program: every serving iteration
packs its work onto a single static [T] token axis (core/ragged.py) — a
plain decode row contributes 1 token, a prefilling row a token-budgeted
prompt chunk, a speculating row its feedback token plus k draft tokens —
and dispatches the same program. One shape means one compile, no
whole-batch padding to the widest row, and speculation that never waits
behind prefill. Admission and eviction only rewrite int32 block tables
between calls, so sequences enter and leave mid-flight with zero
recompilation — the property that lets short requests overtake long
ones instead of idling behind them (the batch-synchronous `GShardDecode`
failure mode this engine replaces).

Host-side the loop is a pipeline of depth two (`StepOnce`): while step n
runs on the device the host admits, builds, places and dispatches step n+1,
and only then fetches and commits step n. Step n+1 does not wait for the
host to see step n's tokens: the scheduler moves the cursors when a step is
dispatched (`Scheduler.AdvanceRaggedStep`: cursor, PREFILL -> DECODE, the
output position, finish by length with the slot and its pages) and the
tokens step n+1 feeds back are gathered from step n's draws on the device
(`_FeedTokens`). Only what needs the values waits for the fetch
(`Scheduler.CommitRaggedStep`: the stream, eos). A row that ends while
the next step already carries it (eos, Cancel) has that step's result
dropped; its extra K/V write lands in pages reserved for it at admission.
A draft source reads the committed token on the host, so an engine with one
runs the same loop at depth one: it retires a step before it builds the
next.

Speculative decoding (serving/spec_decode.py) configures a draft source
(`spec=SelfDraft(...)` or `spec=ModelDraft(...)`): each iteration where
at least one decode row speculates runs a draft pass proposing k tokens
per such row, then the SAME unified step verifies them — spec rows are
just width-(k+1) rows whose gathered logits flow through
`SpecVerifyTokens` inside the one program — and commits each row's
accepted prefix plus a bonus/correction token, rolling write cursors
back over rejected tails. Prefilling neighbors ride the same step, so
spec cycles never wait for pure-decode iterations. At temperature 0
the output streams are token-identical to the non-spec engine (greedy
acceptance keeps exactly the argmax prefix); at temperature > 0
residual speculative sampling preserves each request's seeded output
distribution. Per-request `spec_k` on Submit() opts individual requests
out (0) or caps their draft length.

Sampling: temperature 0 (default) is pure argmax — token-identical to
batch-synchronous `GShardDecode`, the parity bar asserted in tests. With
temperature > 0 (optional top_k) each request samples from its OWN
stream (core/sampling.py): the draw for output position t of a request
with seed s is a pure function of (engine sample_seed, s, t), carried
through the scheduler as per-row `row_seeds`/`row_pos`, so continuations
are replayable no matter which slot or batch neighbors the scheduler
picked.

O(1)-state mixers (core/ssm.py): stacks whose mixers carry fixed-size
recurrent state instead of KV pages plug in unchanged — their decode
state is a [max_batch, ...] per-slot array reset device-side on each
sequence's first chunk (q_pos == 0). The engine takes a mixer census at
construction: hybrid stacks price both resources, and pure-SSM stacks
set `needs_kv_pages=False` so admission is bounded by decode slots only
(the allocator is never charged, so more requests run concurrently at
fixed HBM).

Two front doors:
- async: `Start()` + `Submit(prompt, max_new) -> StreamHandle` — tokens
  stream out per request as they are committed; `Cancel()` mid-flight.
- sync: `RunBatch(prompts, prompt_lens)` — GShardDecode-parity mode:
  submit everything, drive the loop inline, return `[B, max_new]` outputs
  in submission order.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from lingvo_tpu import observe
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.core import sampling
from lingvo_tpu.observe import profile as observe_profile
from lingvo_tpu.observe import schema as observe_schema
from lingvo_tpu.observe import trace as observe_trace
from lingvo_tpu.quant import weights as quant_weights
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import prefix_cache as prefix_cache_lib
from lingvo_tpu.serving import scheduler as scheduler_lib
from lingvo_tpu.serving import spec_decode
from lingvo_tpu.serving import state_layout

_END = object()   # stream sentinel

# The spans of one iteration of the loop. Each is a
# jax.profiler.TraceAnnotation: with a profiler trace running it lands on the
# host plane of the same .xplane.pb as the device ops, on the profiler's
# clock; with none it is a flag test. An iteration dispatches step k and then
# retires step k-1 (step k itself where a draft source keeps depth one), so
# its first six spans belong to step k and its last three to step k-1.
#   TraceAnnotation lingvo/serve/step         one StepOnce (its arguments:
#                                             those of the step it launched)
#   TraceAnnotation lingvo/serve/lock_wait    both takings of the engine lock
#   TraceAnnotation lingvo/serve/admit        _AdmitPhase
#   TraceAnnotation lingvo/serve/build        BuildRaggedStep, block-table copy,
#                                             AdvanceRaggedStep, step counters
#   TraceAnnotation lingvo/serve/draft        the draft pass (spec engines)
#   TraceAnnotation lingvo/serve/h2d          the jnp.asarray placements
#   TraceAnnotation lingvo/serve/dispatch     the feed gather and the step
#                                             program: _compile_log.Call returning
#   TraceAnnotation lingvo/serve/device_wait  np.asarray(sampled, out, alen) of
#                                             the step being retired
#   TraceAnnotation lingvo/serve/commit       CommitRaggedStep, counters, events
_STEP_SPAN = "lingvo/serve/step"
_SEGMENT_SPANS = {
    "lock_wait": "lingvo/serve/lock_wait", "admit": "lingvo/serve/admit",
    "build": "lingvo/serve/build", "draft": "lingvo/serve/draft",
    "h2d": "lingvo/serve/h2d", "dispatch": "lingvo/serve/dispatch",
    "device_wait": "lingvo/serve/device_wait",
    "commit": "lingvo/serve/commit"}
_SEGMENTS = observe_trace.STEP_SEGMENTS
# Set-up has spans of its own, `lingvo/setup/<phase>` (observe.profile's
# start-up record keeps each as an entry too): `build`, the constructor, with
# `int8_theta`, `states` (InitPagedDecodeState) and `layout`
# (state_layout.Detect) inside it where they run; `compile_step`,
# _CompileStep; and, in the record only, `first_steps`: from Start()'s
# return (or the first StepOnce of a caller who drives the loop) to the
# commit of the first step that emitted a token.
_STARTUP = observe_profile.Startup()


class _StepSpans:
  """Where one engine step's host time goes: the step is cut into the
  segments of observe.trace.STEP_SEGMENTS at the points the step code names
  (`To`), each timed on the recorder's clock and wrapped in a
  TraceAnnotation, and `End` writes the one StepTrace record. The segments
  tile the step, and `loop` is the time since the previous step's end, so a
  record adds up to the step's period. Engine-loop thread only."""

  def __init__(self, recorder):
    self._recorder = recorder
    self._clock = recorder.clock if recorder is not None else time.perf_counter
    self._last_end = None     # end of the previous recorded step
    self._step_ann = None
    self._seg_ann = None
    self._acc = None
    self._unit = None         # what compiled while the step's record is open

  def Begin(self):
    self._unit = _STARTUP.OpenUnit("step")
    self._step_ann = jax.profiler.TraceAnnotation(_STEP_SPAN)
    self._t0 = self._t_seg = self._clock()
    self._acc = [0.0] * len(_SEGMENTS)
    self._i = -1

  def _EndSegment(self, now):
    if self._i >= 0:
      self._seg_ann.__exit__(None, None, None)
      self._acc[self._i] += now - self._t_seg

  def To(self, name: str):
    """The step passes into segment `name` (the next one of that name)."""
    now = self._clock()
    self._EndSegment(now)
    self._i = _SEGMENTS.index(name, max(self._i, 0))
    self._t_seg = now
    self._seg_ann = jax.profiler.TraceAnnotation(_SEGMENT_SPANS[name])

  def _Close(self, **metadata):
    now = self._clock()
    self._EndSegment(now)
    if metadata:
      self._step_ann.set_metadata(**metadata)
    self._step_ann.__exit__(None, None, None)
    self._step_ann = self._seg_ann = None
    _STARTUP.CloseUnit(self._unit)
    return now

  def Abandon(self):
    """An iteration that launched nothing (or raised): no record, though it
    may have retired the last step in flight; its time counts as the next
    step's `loop`."""
    if self._step_ann is not None:
      self._Close()

  def End(self, step: int, valid_tokens: int, prefill_tokens: int, rows: int,
          counters=None):
    now = self._Close(step=step, valid_tokens=valid_tokens,
                      prefill_tokens=prefill_tokens, rows=rows)
    loop_s = self._t0 - self._last_end if self._last_end is not None else 0.0
    self._last_end = now
    unit = self._unit
    if unit.compile_s:
      # a step that compiled says what: a late compile is a stall with a name
      counters = dict(counters or {}, compile_fun_names=list(unit.fun_names))
    if self._recorder is not None:
      self._recorder.StepDone(step, self._t0, loop_s, self._acc,
                              valid_tokens, prefill_tokens, rows, counters,
                              compile_s=unit.compile_s)


def _MoeCountLeaves(states, name="routed", width=None):
  """The `routed` leaves of a decode state (core/moe.DroplessMoELayer:
  tokens by held expert of the newest step), each as [layers of it,
  experts]; None where the stack has no expert layer. name='elsewhere',
  width=1: the pairs a layer that holds a share of its experts sent nowhere
  here, [layers of it, 1]; None where every layer holds all it routes over."""
  leaves = [leaf.reshape(-1, width or leaf.shape[-1]) for path, leaf in
            jax.tree_util.tree_flatten_with_path(states)[0]
            if str(getattr(path[-1], "key", getattr(path[-1], "name", "")))
            == name]
  return leaves or None


def _FeedTokens(prev_sampled, tok_ids):
  """The packed token stream a step receives, from the one the host wrote:
  a negative entry -1 - j (scheduler.RaggedBatch) is replaced by draw j of
  the previous step, which the host has not fetched yet. A program of its
  own, tiny, so that the step program is handed plain token ids."""
  src = jnp.clip(-1 - tok_ids, 0, prev_sampled.shape[0] - 1)
  return jnp.where(tok_ids < 0, prev_sampled[src].astype(tok_ids.dtype),
                   tok_ids)


class StreamHandle:
  """Per-request streaming output + lifecycle handle."""

  def __init__(self, req_id, engine, submit_time: float):
    self.id = req_id
    self._engine = engine
    self._q = queue.Queue()
    self._tokens = []
    self._done = threading.Event()
    self.finish_reason: Optional[str] = None
    self.submit_time = submit_time
    self.admit_time: Optional[float] = None
    self.first_token_time: Optional[float] = None
    self.finish_time: Optional[float] = None

  # engine-side
  def _Push(self, token: int):
    if self.first_token_time is None:
      self.first_token_time = time.perf_counter()
    self._tokens.append(token)
    self._q.put(token)

  def _Finish(self, reason: str):
    self.finish_reason = reason
    self.finish_time = time.perf_counter()
    self._done.set()
    self._q.put(_END)

  # user-side
  def Tokens(self, timeout: Optional[float] = None):
    """Yields tokens as they are generated; returns on completion."""
    while True:
      item = self._q.get(timeout=timeout)
      if item is _END:
        return
      yield item

  def Result(self, timeout: Optional[float] = None) -> list:
    """Blocks until the request finishes; returns all generated tokens."""
    if not self._done.wait(timeout=timeout):
      raise TimeoutError(f"request {self.id!r} still running")
    return list(self._tokens)

  def Cancel(self) -> bool:
    return self._engine.Cancel(self.id)

  @property
  def done(self) -> bool:
    return self._done.is_set()


class ServingLoop:
  """Continuous-batching decode service over a block-table page pool."""

  @observe_profile.InPhase("build")
  def __init__(self, task, theta, *, page_size: int, num_pages: int,
               max_batch: int, max_seq_len: int, prefill_chunk: int = 8,
               default_max_new: int = 32, eos_id: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0,
               sample_seed: int = 0, kv_cache_dtype: Optional[str] = None,
               serve_int8_weights: bool = False, spec=None,
               prefix_cache=None, trace=True, metrics_registry=None,
               serve_port: Optional[int] = None, watchdog=None,
               prefill_token_budget: Optional[int] = None,
               prefix_swap_persist: bool = False,
               scheduler_mode: str = "fifo",
               tenant_quotas=None, tenant_weights=None):
    """task: a TransformerLm-style task exposing InitPagedDecodeState /
    RaggedStep, whose `stack` answers MixerLayers() (docs/serving_engine.md,
    "What the engine asks of a stack"); a draft source also runs its
    PagedStep. num_pages: allocator-owned pages (the device pool gets one
    extra trash page). max_seq_len: static per-sequence capacity bound
    (block-table width = ceil(max_seq_len / page_size)).
    temperature/top_k/sample_seed: sampling controls (module docstring);
    temperature <= 0 compiles to the pre-sampling argmax program.
    kv_cache_dtype: overrides the task's layer-level kv_cache_dtype for
    this engine's page pool (None keeps it; see quant/kv.py) — 'int8'
    turns on quantize-on-write KV pages with scale sidecars.
    serve_int8_weights: rewrite the served theta so decode projections run
    as `Int8Einsum` integer matmuls (quant/weights.py); the float theta is
    untouched, only this engine's copy is rewritten.
    spec: optional speculative-decoding draft source —
    `spec_decode.SelfDraft` (early-exit over the same theta) or
    `spec_decode.ModelDraft` (independent pageless draft model). None
    serves without speculation.
    prefix_cache: cross-request KV prefix sharing
    (serving/prefix_cache.py) — None (default) admits without sharing,
    True builds a fresh PrefixCache over this engine's pool, or pass a
    PrefixCache instance (rebound via Bind — a cache built against a
    different pool or kv dtype is invalidated, never cross-shared). Requires an attention-only stack: O(1)-state
    mixers carry recurrent state the cache can neither share nor skip.
    trace: per-request lifecycle tracing (observe/trace.py) — True (the
    default) builds a fresh TraceRecorder, False disables, or pass a
    TraceRecorder to share/configure one. metrics_registry: the observe.MetricsRegistry
    this engine publishes through (None = a fresh per-engine registry, so
    replicas and tests stay isolated).
    serve_port: opt-in fleet endpoints (observe/export.py) — an integer
    starts a StatusServer on that port (0 = ephemeral, read
    `self.status_server.port`) serving /metrics, /statusz, /traces and
    /healthz over this engine's registry/Stats()/trace; the server stops
    with Stop(). watchdog: stall watchdog (observe/watchdog.py) — True
    builds a default StallWatchdog on this engine's registry, or pass a
    configured StallWatchdog (capture logdir, injectable clock); the
    engine heartbeats it per step and feeds it queue observations, and
    /healthz runs its Check() at scrape time.
    prefill_token_budget: prompt tokens the packed step reserves beyond
    the worst-case decode tokens (defaults to prefill_chunk); decode
    capacity left idle by empty slots flows to prefill on top of it.
    prefix_swap_persist: what UpdateTheta does to the prefix cache —
    False (default) drops the whole radix tree (Invalidate), True keeps
    the tree and marks every page stale (MarkStale): stale pages are
    never served, but one warm re-prefill per live prefix refreshes its
    nodes in place, so hit_tokens recover without a cold tree restart.
    Per-swap override via UpdateTheta(persist_prefix=...).
    scheduler_mode: 'fifo' (default, the bit-exact legacy admission
    path) or 'priority' — SLO classes, per-tenant quotas, weighted-fair
    admission, and preemption by KV page spill to a host tier
    (serving/scheduler.py module docstring). The engine supplies the
    device halves: serving/state_layout.py's jitted gather/scatter over
    every paged leaf by whole pages (spilled KV round-trips bitwise, int8
    scale sidecars ride along) and over every O(1)-mixer state leaf by
    slot. tenant_quotas: {tenant: (rate, burst) | TokenBucket} token-
    rate quotas enforced at Submit (QuotaExceeded before a handle is
    created). tenant_weights: {tenant: weight} for weighted-fair
    admission within a priority class.
    """
    assert page_size >= 1 and num_pages >= 1 and max_batch >= 1
    assert max_seq_len >= page_size
    self._task = task
    self.serve_int8_weights = bool(serve_int8_weights)
    if serve_int8_weights:
      with _STARTUP.Phase("int8_theta"):
        theta, _ = quant_weights.Int8ServingTheta(theta)
    self._theta = theta
    self.page_size = page_size
    self.num_pages = num_pages
    self.max_batch = max_batch
    self.prefill_chunk = prefill_chunk
    self.default_max_new = default_max_new
    self.eos_id = eos_id
    self.temperature = float(temperature)
    self.top_k = int(top_k)
    self.sample_seed = int(sample_seed)
    # census BEFORE allocating: which resource(s) this stack's decode state
    # occupies, and the effective cache dtype, which prices a page
    census = kv_cache.StackCensus(task, kv_cache_dtype)
    self._mixer_layers = task.stack.MixerLayers()
    self._page_readers = [m for m, _ in self._mixer_layers
                          if kv_cache.ReadsPages(m)]
    self.kv_cache_dtype = census["kv_cache_dtype"]
    self.kv_bytes_per_token = census["kv_bytes_per_token"]
    self._kv_quantized = self.kv_cache_dtype == "int8"
    self._kv_override = kv_cache_dtype
    self.mixers = {k: census[k] for k in (
        "num_attention", "num_ssm", "decode_state_bytes_per_slot")}
    # unified ragged step geometry (the widest row a step admits also sizes
    # what a row of the window kind holds): see below
    self.prefill_token_budget = int(prefill_token_budget or prefill_chunk)
    # layers of two kinds (full attention beside a sliding window) draw
    # from ONE pool of uniform pages, a page a layer of the scanned block
    # (kv_cache.KindPages): `num_pages` stays the caller's one number, pages
    # of page_size tokens at EVERY layer's bytes, so the pool has that many
    # times the block's layers, each of that fraction of the bytes
    windows = getattr(task.stack, "PageWindows", lambda: None)()
    block = len(windows) if windows else 1
    self.alloc = kv_cache.PageAllocator(
        num_pages * block, page_size,
        page_bytes=page_size * self.kv_bytes_per_token // block)
    table_pages = self.alloc.PagesFor(max_seq_len)
    self._kind_pages = None
    if windows:
      refused = [name for name, on in (
          ("prefix_cache", prefix_cache not in (None, False)),
          ("spec (a draft source)", spec is not None),
          ("scheduler_mode='priority' (preemption by page spill)",
           scheduler_mode != "fifo")) if on]
      if refused:
        raise ValueError(
            f"{', '.join(refused)}: not over a stack whose attention layers "
            f"are of two kinds (windows by layer of the block {windows}, "
            "0 = full): those paths know one block table a sequence, and a "
            "window layer lets go of pages a prefix, a rollback or a restore "
            "would read")
      self._kind_pages = kv_cache.KindPages(
          self.alloc, windows, max(1, self.prefill_token_budget), max_batch,
          table_pages)
    ragged_only = sorted({type(m).__name__ for m, _ in self._mixer_layers
                          if getattr(m, "ragged_only", False)})
    if spec is not None and ragged_only:
      raise ValueError(
          f"spec (a draft source): {', '.join(ragged_only)} serves through "
          "the packed step alone; a draft pass and its rollback run the dense "
          "decode contracts (InitStates, ExtendStep, Prefill, PagedStep), "
          "which it does not have")
    self.state_pool = None
    if self.mixers["num_ssm"] > 0:
      self.state_pool = kv_cache.StateSlotPool(
          max_batch, self.mixers["decode_state_bytes_per_slot"])
    # global prefix cache: opt-in KV page sharing across requests. Gated
    # to attention-only stacks — an SSM/hybrid row's recurrent state must
    # replay EVERY prompt token, so skipping cached prefill would decode
    # against wrong state (and the state itself is per-slot, unshareable).
    self.prefix_cache = None
    if prefix_cache is not None and prefix_cache is not False:
      if self.mixers["num_attention"] == 0 or self.mixers["num_ssm"] > 0:
        raise ValueError(
            "prefix_cache requires an attention-only stack: O(1)-state "
            f"mixers (census {self.mixers}) carry recurrent state that "
            "cannot be shared across requests or skipped by cached prefill")
      self.prefix_cache = (
          prefix_cache if isinstance(prefix_cache, prefix_cache_lib.PrefixCache)
          else prefix_cache_lib.PrefixCache())
      self.prefix_cache.Bind(self.alloc, self.kv_cache_dtype)
    self.prefix_swap_persist = bool(prefix_swap_persist)
    self.sched = scheduler_lib.Scheduler(
        max_batch, self.alloc, table_pages,
        needs_kv_pages=self.mixers["num_attention"] > 0,
        state_pool=self.state_pool, prefix_cache=self.prefix_cache,
        scheduler_mode=scheduler_mode, tenant_quotas=tenant_quotas,
        tenant_weights=tenant_weights, kind_pages=self._kind_pages)
    self.scheduler_mode = scheduler_mode
    # device halves of preemption spill/restore (priority mode): whole-
    # page gather/scatter across the paged leaves, slot-row gather/
    # scatter across the O(1)-mixer state leaves. All four run under the
    # engine lock on the loop thread (Admit is only called from
    # _AdmitPhase), so mutating self._states here is safe.
    if scheduler_mode == "priority":
      if self.mixers["num_attention"] > 0:
        self.sched.spill_fn = self._SpillPages
        self.sched.restore_fn = self._RestorePages
      if self.state_pool is not None:
        self.sched.state_spill_fn = self._SpillStateRow
        self.sched.state_restore_fn = self._RestoreStateRow
    # which leaves of the decode state are pages or a slot's, and the
    # gather / scatter over them: built on first use (_Layout); an engine
    # with no prefix cache, no draft tree and FIFO admission never is
    self._layout = None
    # pool page num_pages (the +1) is the trash page padding writes hit;
    # num_slots sizes the per-slot O(1) mixer states (attention ignores it);
    # the kv dtype override is a static string arg (hashable)
    init_fn = jax.jit(task.InitPagedDecodeState, static_argnums=(1, 2, 3, 4))
    with _STARTUP.Phase("states"):
      self._states = init_fn(theta, self.alloc.num_pages + 1, page_size,
                             max_batch, kv_cache_dtype)
    # donate the pool into each step off-cpu (XLA:CPU can't alias + warns)
    donate = (1,) if jax.default_backend() != "cpu" else ()
    # observability (observe/): per-engine metrics registry, per-request
    # lifecycle trace, and one-shot compile records for the step programs
    self.metrics = (metrics_registry if metrics_registry is not None
                    else observe.MetricsRegistry("serving"))
    self.trace = (trace if isinstance(trace, observe.TraceRecorder)
                  else (observe.TraceRecorder() if trace else None))
    self._compile_log = observe.CompileLog(
        registry=self.metrics, namespace="serving/compile", donate=donate)
    self._spans = _StepSpans(self.trace)
    # speculative decoding: the runner owns the draft programs and (for
    # ModelDraft) the draft model's recurrent state
    self.spec = None
    if spec is not None:
      self.spec = spec_decode.SpecRunner(
          spec, task=task, theta=theta, max_batch=max_batch,
          page_size=page_size, prefill_chunk=prefill_chunk,
          temperature=self.temperature, top_k=self.top_k,
          sample_seed=self.sample_seed)
    # unified ragged step geometry: T packed tokens cover every slot's
    # worst-case decode width (1 + draft k) plus a prefill token budget;
    # wmax is the widest single row the one compiled program admits
    # a speculating row is 1 root + w*k tree nodes wide (chain: w == 1)
    spec_width = ((1 + self.spec.w * self.spec.k)
                  if self.spec is not None else 1)
    self._ragged_t = max_batch * spec_width + self.prefill_token_budget
    self._ragged_wmax = max(spec_width, self.prefill_token_budget)
    # the width the step program's row-wise blocks run when the step's live
    # tokens fit it (core/ragged.BuildLiveWidth); `narrow_steps` counts
    # those steps
    self._narrow_rows = ragged_lib.DecodeWidth(self._ragged_t,
                                               self._ragged_wmax)
    # the token columns whose logits something reads: a draw a slot, and
    # under a draft source the slot's verify columns. The step program's
    # head runs over them alone, unless they are no fewer than the packed
    # tokens (a wide tree over a tiny budget)
    self.head_rows = min(
        max_batch * (1 + (spec_width if self.spec is not None else 0)),
        self._ragged_t)
    self._ragged_fn = self._BuildRaggedFn(task, donate)
    self._feed_fn = jax.jit(_FeedTokens)
    # dispatched steps whose tokens are still on the device, oldest first:
    # (batch, sampled) or, with a draft source, (batch, sampled, out, alen).
    # Only the thread that drives StepOnce touches it; the scheduler counts
    # them for HasWork's callers.
    self._in_flight = collections.deque()
    self._zero_qlogits = None   # lazy [B, w*k, V] f32 (no-draft spec steps)
    # silent-fallback visibility: classify ONCE which attention path the
    # compiled step will take, and count ineligible (dense-fallback) steps
    self.paged_path = self._ClassifyPath()
    # block size of the ragged attend kernel at this stack's shapes (0: no
    # attention layer, or one with kernels of its own, PowerRetention): what
    # the block-fill counters' reader divides by (benchmarks/harness)
    attens = [m for m in self._page_readers if hasattr(m, "RaggedQueryBlock")]
    self._attend_bq = (attens[0].RaggedQueryBlock(page_size, kv_cache_dtype)
                       if attens else 0)
    # the attend kernels the step program calls, and the plans it builds
    kernel_keys = [k for k in task.stack.RaggedPlanKeys(self._states)
                   if k.kernel]
    self.attend_calls = len(kernel_keys)
    self.attend_plans = len(set(kernel_keys))
    # expert layers: their [layers, experts] token counts leave the step
    # program beside the tokens (None: the stack has none)
    self._moe_layers = _MoeCountLeaves(self._states)
    self._moe_shares = _MoeCountLeaves(self._states, "elsewhere", 1) is not None
    # layers that read pages another layer owns (0: the stack has none)
    self._shared_kv_read_layers = getattr(
        task.stack, "SharedKvReadLayers", lambda: 0)()
    self._handles: dict = {}
    # counters live in the registry under serving/* (schema is the single
    # source of the key set); Stats() maps them back to the plain keys.
    # All Inc() calls happen under the engine lock, so Stats() — which
    # also holds it — reads a mutually-consistent set.
    self._counters = {
        k: self.metrics.Counter(f"serving/{k}")
        for k in observe_schema.ENGINE_COUNTER_KEYS}
    # what a step's rows cost the stack's mixers, in their own counters: the
    # stack's word (core/ragged.StackStepCounts), asked once
    step_counts = task.stack.StepCounts(self._states, ragged_lib.StepGeometry(
        page_size, kv_cache_dtype, max_batch, self._ragged_t, table_pages))
    self._step_counts = [([self._counters[k] for k in c.names], c.count)
                         for c in step_counts]
    self._record_counts = [k for c in step_counts if c.in_record
                           for k in c.names]
    # engine configuration facts + live sub-surfaces. Section callbacks
    # deliberately read WITHOUT the engine lock (a registry snapshot
    # holding the registry lock must never wait on the engine lock —
    # lock-order inversion against the hot path's counter Incs); the
    # atomic consistent read is Stats().
    self.metrics.Gauge("serving/paged_path").Set(self.paged_path)
    self.metrics.Gauge("serving/kv_cache_dtype").Set(self.kv_cache_dtype)
    self.metrics.Gauge("serving/kv_bytes_per_token").Set(
        self.kv_bytes_per_token)
    self.metrics.Gauge("serving/serve_int8_weights").Set(
        self.serve_int8_weights)
    self.metrics.Gauge("serving/head_rows").Set(self.head_rows)
    self.metrics.Gauge("serving/attend_calls").Set(self.attend_calls)
    self.metrics.Gauge("serving/attend_plans").Set(self.attend_plans)
    self.metrics.SectionFn("scheduler", self.sched.Stats)
    self.metrics.SectionFn("kv_pages", (self._kind_pages or self.alloc).Stats)
    self.metrics.SectionFn(
        "prefix_cache",
        self.prefix_cache.Stats if self.prefix_cache is not None
        else observe_schema.DisabledPrefixCacheStats)
    if self.state_pool is not None:
      self.metrics.SectionFn("state_slots", self.state_pool.Stats)
    if self.trace is not None:
      self.metrics.SectionFn("trace", self.trace.Stats)
    self._h_queue_wait = self.metrics.Histogram("serving/queue_wait_s")
    self._h_ttft = self.metrics.Histogram("serving/ttft_s")
    self._h_tpot = self.metrics.Histogram("serving/tpot_s")
    self._h_queue_wait_cls: dict = {}   # SLO class -> queue-wait Histogram
    self._pages_of: dict = {}   # req_id -> pages granted at admission
    self._profile_window = None
    self._lock = threading.RLock()
    self._work = threading.Condition(self._lock)
    self._thread: Optional[threading.Thread] = None
    self._running = False
    # the start-up record's `first_steps` phase: None until the loop is
    # started or first stepped, open until a step's commit emits a token,
    # False from then on
    self._first_steps = None
    self._cancel_open = False   # Stop(drain=False) asked; _CancelOpen answers
    self._seq_counter = 0
    self._adopt_counter = 0   # transient page-handoff allocation owners
    # stall watchdog: StepOnce heartbeats + queue observations feed it;
    # the /healthz scrape thread (or a test) runs Check() — liveness must
    # be evaluated on a thread a hung step loop can't take down
    self.watchdog = None
    if watchdog is not None and watchdog is not False:
      self.watchdog = (watchdog
                       if isinstance(watchdog, observe.StallWatchdog)
                       else observe.StallWatchdog(self.metrics))
    # fleet-facing endpoints, opt-in via serve_port (0 = ephemeral port)
    self.status_server = None
    if serve_port is not None:
      self.status_server = observe.StatusServer(
          serve_port, registry=self.metrics, name="serving",
          statusz_fn=self.Stats, trace=self.trace,
          watchdog=self.watchdog).Start()

  # -- path classification ---------------------------------------------------

  def _Layout(self) -> state_layout.StateLayout:
    """The decode state's layout (serving/state_layout.py), detected the
    first time something moves state by page, by token or by slot."""
    if self._layout is None:
      with _STARTUP.Phase("layout"):
        self._layout = state_layout.Detect(
            self._task, self._theta, self.num_pages + 1, self.page_size,
            self.max_batch, self._kv_override)
    return self._layout

  def _ClassifyPath(self) -> str:
    """'pallas[-int8]' | 'xla[-int8]' | 'dense' | 'ssm' — what the step
    program's reads of the page pool lower to.

    A dense fallback (ineligible attention config) is CORRECT but not
    paged-fast; it must be visible, never silent (ISSUE satellite). With
    an int8 pool the fallback still reads quantized pages (gather +
    dequantize), but loses the in-kernel dequant — equally worth
    surfacing. 'ssm' = no attention layer at all: the page pool is never
    read and classification is about the recurrent-state path instead."""
    attens = self._page_readers
    if not attens:
      return "ssm"
    if self._kv_quantized:
      if not all(a.QuantizedDecodeEligible(self.page_size) for a in attens):
        return "dense"
      suffix = "-int8"
    else:
      if not all(a.BlockDecodeEligible(self.page_size) for a in attens):
        return "dense"
      suffix = ""
    base = "pallas" if jax.default_backend() == "tpu" else "xla"
    return base + suffix

  # -- the unified ragged step program ---------------------------------------

  def _BuildRaggedFn(self, task, donate):
    """Jits THE serving step: packed-token forward + sampling (+ verify).

    One program covers every iteration shape: prefill chunks, plain
    decode rows, and spec-verify rows are just rows of different length
    on the same [T] token axis (core/ragged.py). The commit consumes one
    column per row (a decode row's only column, a finishing prefill's
    last prompt column), so the final norm, the head and the draw run
    over those columns and the verify lane's alone (`head_rows` of them,
    gathered before the final norm: _HeadStep), each row drawing from its
    own (seed, output-position) stream; `sampled` stays [T], the draws
    scattered to their columns, because the next step's feed, the commit
    and whoever stands in for this program index it by column. When a draft
    source is configured the verify lane is always computed (static
    structure): rows with row_k == 0 flow through SpecVerifyTokens as
    all-invalid and their column-0 output is exactly the plain draw, so
    no-draft steps run the SAME program with zero q_logits rather than a
    second compiled shape.

    Tree speculation (draft width w > 1) stays the SAME one program:
    speculating rows pack a w-ary token tree in DFS order, the verify
    lane rebuilds DFS-ordered target logits from the packed columns and
    runs SpecVerifyTree with a static branch table, the accepted path's
    K/V is gathered/scattered into the canonical chain slots inside the
    same jit (no second program, no host round-trip), and hybrid-SSM
    rows column-select the accepted LEAF's tree-scan trajectory. Width
    w == 1 engines compile the EXACT chain program below — chain
    speculation is the degenerate tree, bitwise.
    """
    temp, topk = self.temperature, self.top_k
    base_key = self.sample_seed
    b = self.max_batch
    spec_k = self.spec.k if self.spec is not None else 0
    spec_w = self.spec.w if self.spec is not None else 1
    collect = self.spec is not None and self.mixers["num_ssm"] > 0

    t = self._ragged_t
    narrow = self.head_rows < t   # else the full head, gathered after

    def _HeadStep(theta, states, tok_ids, rows, tables, seeds, pos,
                  lane_cols=None):
      """The forward pass, with the head over the columns something reads:
      each slot's draw column (its row's last token: a decode row's only
      one, a finishing prompt's last; of a mid-prompt chunk nothing is
      read) and after them `lane_cols` [m], the verify lane's. A draw is a
      pure function of (engine seed, row seed, output position), so it is
      the one its column had when every column drew. Returns (sampled [T]
      int32, slot b's draw at its column and 0 elsewhere; the lane's logits
      [m, V]; new_states)."""
      with observe.Scope("head_sample"):
        draw_cols = jnp.take_along_axis(
            rows.row_cols, jnp.maximum(rows.row_len - 1, 0)[:, None],
            axis=1)[:, 0]                                      # [B]
        cols = (draw_cols if lane_cols is None
                else jnp.concatenate([draw_cols, lane_cols]))
      logits, new_states = task.RaggedStep(
          theta, tok_ids[None], states, tables, rows, ssm_col_states=collect,
          head_cols=cols if narrow else None)
      with observe.Scope("head_sample"):
        logits = logits[0] if narrow else logits[0][cols]      # [n, V]
        draws = sampling.SampleFromLogits(
            logits[:b], jax.random.PRNGKey(base_key), temperature=temp,
            top_k=topk, row_seeds=seeds, positions=pos)
        # an empty slot's row_cols point at column 0, which is a live
        # row's: its draw is sent out of range and dropped
        sampled = jnp.zeros((t,), jnp.int32).at[
            jnp.where(rows.row_len > 0, draw_cols, t)].set(draws,
                                                           mode="drop")
      return sampled, logits[b:], new_states

    if spec_k == 0:
      def _RaggedStep(theta, states, tok_ids, rows, tables, seeds, pos):
        sampled, _, new_states = _HeadStep(theta, states, tok_ids, rows,
                                           tables, seeds, pos)
        routed = _MoeCountLeaves(new_states)
        if routed is None:
          return sampled, new_states
        # [layers, experts] tokens by expert of this step: a copy (the
        # states are donated to the next step before this one is fetched)
        counts = jnp.concatenate(routed, axis=0)
        elsewhere = _MoeCountLeaves(new_states, "elsewhere", 1)
        if elsewhere is not None:
          # layers that hold a share of their experts: one more column, the
          # pairs whose expert lives on another chip
          counts = jnp.concatenate(
              [counts, jnp.concatenate(elsewhere, axis=0)], axis=1)
        return sampled, counts, new_states
    elif spec_w == 1:
      def _RaggedStep(theta, states, tok_ids, rows, tables, seeds, pos,
                      row_k, q_logits):
        # verify lane: each row's first spec_k+1 token columns, gathered
        # back to [B, k+1] — prefill/no-draft rows gather garbage that
        # draft_valid masks out of acceptance entirely
        key = jax.random.PRNGKey(base_key)
        vcols = rows.row_cols[:, :spec_k + 1]
        sampled, v_logits, new_states = _HeadStep(
            theta, states, tok_ids, rows, tables, seeds, pos,
            vcols.reshape(-1))
        v_logits = v_logits.reshape(b, spec_k + 1, -1)
        d_toks = tok_ids[vcols[:, 1:]]
        draft_valid = (jnp.arange(spec_k, dtype=jnp.int32)[None]
                       < row_k[:, None])
        out, alen = sampling.SpecVerifyTokens(
            v_logits, d_toks, q_logits, key, temperature=temp, top_k=topk,
            row_seeds=seeds, row_pos=pos, draft_valid=draft_valid)
        if collect:
          # SSM trajectory restore: spec rows roll back to the accepted
          # column; every other row keeps the state after its LAST real
          # token (columns past row_len are identity steps, so the
          # clipped index is exact for 0-token rows too)
          restore = jnp.where(row_k > 0, alen,
                              jnp.clip(rows.row_len - 1, 0, None))
          new_states = spec_decode._SelectAcceptedCols(new_states, restore)
        return sampled, out, alen, new_states
    else:
      r = spec_w * spec_k
      ps = self.page_size
      trash_page = self.num_pages        # the pool's padding-write page
      # tree KV repair moves tokens by each paged leaf's (page, offset)
      # axes; a stack with no attention layer has nothing to repair
      layout = self._Layout() if self.mixers["num_attention"] > 0 else None

      def _RepairKv(states, tables, rows, row_k, alen, wbr):
        # Moves the accepted path's K/V (and int8 scale sidecars) from
        # its DFS tree slots to the canonical chain slots q_pos+1..
        # q_pos+m, so the committed cache is bit-identical to a chain
        # that decoded the same tokens. Branch-0 wins are pure identity
        # copies (src == dst); inactive (row, depth) pairs copy the
        # trash page onto itself so duplicate scatter indices can never
        # land on live pages.
        q_pos = rows.row_q_pos.astype(jnp.int32)
        dd = jnp.arange(1, spec_k + 1, dtype=jnp.int32)[None]    # [1, K]
        m = jnp.minimum(alen, row_k)[:, None]
        active = (row_k[:, None] > 0) & (dd <= m)
        src_slot = (q_pos[:, None] + 1
                    + wbr[:, None] * row_k[:, None] + dd - 1)
        dst_slot = q_pos[:, None] + dd
        cap = tables.shape[1] * ps
        src_slot = jnp.clip(src_slot, 0, cap - 1)
        dst_slot = jnp.clip(dst_slot, 0, cap - 1)
        bb = jnp.arange(b, dtype=jnp.int32)[:, None]
        sp = jnp.where(active, tables[bb, src_slot // ps], trash_page)
        so = jnp.where(active, src_slot % ps, 0)
        dp = jnp.where(active, tables[bb, dst_slot // ps], trash_page)
        do = jnp.where(active, dst_slot % ps, 0)
        return layout.Copy(states, "token", (sp, so), (dp, do))

      def _RaggedStep(theta, states, tok_ids, rows, tables, seeds, pos,
                      row_k, row_w, q_logits):
        # tree verify lane: draft node j = bi*k + d (the branch-major
        # draft layout) sits at packed column 1 + bi*row_k + d; rows
        # with clamped width/depth leave the tail invalid, so the
        # branch table stays a STATIC arange and per-row shape lives
        # entirely in draft_valid. DFS-ordered target logits are
        # rebuilt so node j's after-distribution is column j + 1 —
        # the SpecVerifyTree contract.
        j = jnp.arange(r, dtype=jnp.int32)
        bi_j, d_j = j // spec_k, j % spec_k
        nvalid = ((bi_j[None] < row_w[:, None])
                  & (d_j[None] < row_k[:, None]))              # [B, R]
        node_col = jnp.where(
            nvalid, 1 + bi_j[None] * row_k[:, None] + d_j[None], 0)
        ntok = jnp.take_along_axis(rows.row_cols, node_col, axis=1)
        key = jax.random.PRNGKey(base_key)
        vcols = jnp.concatenate([rows.row_cols[:, :1], ntok], axis=1)
        sampled, v_logits, new_states = _HeadStep(
            theta, states, tok_ids, rows, tables, seeds, pos,
            vcols.reshape(-1))
        v_logits = v_logits.reshape(b, r + 1, -1)
        d_toks = tok_ids[ntok]
        branches = jnp.broadcast_to(
            jnp.arange(r, dtype=jnp.int32).reshape(1, spec_w, spec_k),
            (b, spec_w, spec_k))
        out, alen, wbr = sampling.SpecVerifyTree(
            v_logits, d_toks, branches, q_logits, key, temperature=temp,
            top_k=topk, row_seeds=seeds, row_pos=pos, draft_valid=nvalid)
        if collect:
          # SSM trajectory restore: the accepted LEAF's packed column —
          # the tree scan threaded states parent-to-child, so the leaf
          # column holds exactly the chain state after root + path
          leaf_col = jnp.where(alen > 0, 1 + wbr * row_k + (alen - 1), 0)
          restore = jnp.where(row_k > 0, leaf_col,
                              jnp.clip(rows.row_len - 1, 0, None))
          new_states = spec_decode._SelectAcceptedCols(new_states, restore)
        if layout is not None:
          new_states = _RepairKv(new_states, tables, rows, row_k, alen,
                                 wbr)
        return sampled, out, alen, new_states

    return jax.jit(_RaggedStep, donate_argnums=donate)

  def _ZeroQLogits(self):
    """All-zero draft logits for spec-engine steps where no row drafted
    (still prefilling): the verify lane runs with draft_valid all-False,
    so the values are never consumed — they only pin the one compiled
    signature. Tree engines widen to the full w*k draft layout."""
    if self._zero_qlogits is None:
      self._zero_qlogits = jnp.zeros(
          (self.max_batch, self.spec.w * self.spec.k,
           self._task.p.vocab_size), jnp.float32)
    return self._zero_qlogits

  # -- prefix-cache support --------------------------------------------------

  def _RunCow(self, admitted):
    """Executes pending copy-on-write page splits for freshly admitted
    sequences (caller holds the lock; the loop thread owns _states)."""
    for seq in admitted:
      for src, dst in seq.cow_pairs:
        self._states = self._Layout().copy(
            self._states, "page", jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32))
      seq.cow_pairs = []

  # -- preemption spill/restore (scheduler_mode='priority') ------------------

  def _SpillPages(self, pages):
    """Scheduler spill callback: device→host copies of whole pages
    across every paged leaf. Copies to host memory are FORCED before
    returning — the scheduler frees the device pages right after, so a
    lazy device view would read reallocated garbage."""
    blocks = self._Layout().gather(self._states, "page",
                                   jnp.asarray(pages, jnp.int32))
    return [np.asarray(b) for b in jax.block_until_ready(blocks)]

  def _RestorePages(self, pages, blocks):
    """Scheduler restore callback: scatters spilled host blocks into the
    freshly allocated device pages (same logical slots, new physical)."""
    self._states = self._Layout().scatter(
        self._states, "page", jnp.asarray(pages, jnp.int32),
        [jnp.asarray(b) for b in blocks])

  def _SpillStateRow(self, slot: int):
    """Scheduler state-spill callback: one slot's O(1)-mixer state rows
    (every slot-axis leaf), forced to host."""
    rows = self._Layout().gather(self._states, "slot", jnp.int32(slot))
    return [np.asarray(r) for r in jax.block_until_ready(rows)]

  def _RestoreStateRow(self, slot: int, rows):
    """Scheduler state-restore callback: lands a spilled state row in
    the (possibly different) slot the sequence resumes in."""
    self._states = self._Layout().scatter(
        self._states, "slot", jnp.int32(slot),
        [jnp.asarray(r) for r in rows])

  def ExportPrefixBlocks(self, prompt):
    """Donor half of the fleet page handoff: gathers this engine's
    cached full-page KV prefix of `prompt` out of its pool. Returns
    (num_pages, blocks) — blocks is the per-paged-leaf [n, ...] device
    array list, (0, []) when nothing is cached. The source pages are
    pinned (Retain) only for the duration of the gather; the blocks are
    copies, so the donor may evict or swap freely afterwards."""
    if self.prefix_cache is None:
      return 0, []
    with self._lock:
      pages, _ = self.prefix_cache.Probe(prompt)
      if not pages:
        return 0, []
      for pg in pages:
        self.alloc.Retain(pg)
      try:
        blocks = self._Layout().gather(self._states, "page",
                                       jnp.asarray(pages, jnp.int32))
        # materialize before unpinning: the gather must read the pages
        # while our Retain still guarantees nobody rewrites them
        blocks = list(jax.block_until_ready(blocks))
      finally:
        for pg in pages:
          self.alloc.Release(pg)
    return len(pages), blocks

  def AdoptPrefix(self, prompt, donor, channel=None) -> int:
    """Receiver half of the fleet page handoff (prefill/decode
    disaggregation, serving/fleet.py): copies `donor`'s cached full-page
    KV prefix for `prompt` into this engine's pool and prefix cache, so
    the next Submit of `prompt` admits as a warm prefix hit and prefill
    covers only the uncached tail. channel: optional transport applied
    to the gathered page blocks between the pools (e.g. the
    parallel/sendrecv.py ppermute lowering for multi-host fleets); None
    copies directly on the shared device. Returns tokens adopted — 0
    when either side has no cache, the donor holds nothing, or this pool
    cannot free enough pages (the caller then just prefills cold)."""
    if self.prefix_cache is None:
      return 0
    n, blocks = donor.ExportPrefixBlocks(prompt)
    if n == 0:
      return 0
    if channel is not None:
      blocks = channel.Transfer(blocks)
    with self._lock:
      already = self.prefix_cache.PeekHitTokens(prompt)
      if already >= n * self.page_size:
        return 0   # warm already — don't churn pages for a worse copy
      if self.alloc.num_free < n:
        self.prefix_cache.EvictForPressure(n - self.alloc.num_free)
        if self.alloc.num_free < n:
          return 0
      self._adopt_counter += 1
      owner = ("_adopt", self._adopt_counter)
      pages = self.alloc.Allocate(owner, n)
      self._states = self._Layout().scatter(
          self._states, "page", jnp.asarray(pages, jnp.int32), blocks)
      # Insert retains what it keeps; Free drops our allocation ref, so
      # unadopted pages (a racing insert won) go straight back to the pool
      self.prefix_cache.Insert(prompt, pages)
      self.alloc.Free(owner)
    return n * self.page_size

  def UpdateTheta(self, theta, persist_prefix: Optional[bool] = None):
    """Hot-swaps the served checkpoint. Every cached prefix page holds
    K/V computed under the OLD theta — serving one to a new request
    would silently mix checkpoints — so the prefix cache is either
    dropped wholesale (Invalidate, the default) or, when
    `persist_prefix` (falling back to the engine's prefix_swap_persist
    knob) is True, kept as a tree of STALE nodes that the next prefill
    of each prefix refreshes in place (PrefixCache.MarkStale). In-flight
    sequences continue under the new theta, as with any mid-serving
    swap; a ModelDraft's independent draft theta is not touched (stale
    drafts cost acceptance rate, never correctness — every proposal is
    verified against the live theta)."""
    with self._lock:
      if self.serve_int8_weights:
        theta, _ = quant_weights.Int8ServingTheta(theta)
      self._theta = theta
      if self.prefix_cache is not None:
        persist = (self.prefix_swap_persist if persist_prefix is None
                   else persist_prefix)
        if persist:
          self.prefix_cache.MarkStale()
        else:
          self.prefix_cache.Invalidate()

  # -- async API -------------------------------------------------------------

  @observe_profile.InPhase("compile_step")
  def _CompileStep(self):
    """Builds THE step program here, in the thread that starts the engine,
    from an idle step's arguments placed as _Dispatch places a real one's
    (the program has one shape whatever a step holds). Left to its first
    dispatch the loop's own thread would build it, beside the thread that
    waits for it: on the benchmark's host the compile cache's fetch of it
    took 2.0-2.4 s there against 0.4-0.5 s here (`brumby14b`), 3.6 against
    0.9 (`nemotron3nano`): PERF.md section 6, PR 51.
    A draft source's step takes arguments of a draft pass: built at its
    first dispatch, as before."""
    if self.spec is not None:
      return
    b, t = self.max_batch, self._ragged_t
    desc = ragged_lib.BuildRaggedRows(
        np.zeros((b,), np.int32), np.ones((b,), np.int32), t,
        self._ragged_wmax, None)
    tables = (self._kind_pages.tables if self._kind_pages is not None
              else self.sched.block_tables)
    # placed from the host's arrays, as _Dispatch places a step's: a
    # `jnp.zeros` here would be one more program to compile and to fetch
    zeros = jnp.asarray(np.zeros((b,), np.int32))
    tok_ids = jnp.asarray(np.zeros((t,), np.int32))
    self._compile_log.Compile(
        "ragged", self._ragged_fn, self._theta, self._states, tok_ids,
        ragged_lib.RaggedRows(*(jnp.asarray(m) for m in desc)),
        jnp.asarray(np.array(tables)), zeros, zeros)
    self._compile_log.Compile("feed", self._feed_fn, tok_ids, tok_ids)

  def Start(self):
    with self._lock:
      if self._running:
        return self
      self._CompileStep()
      if self._first_steps is None:
        self._first_steps = _STARTUP.OpenPhase("first_steps")
      self._running = True
      self._thread = threading.Thread(target=self._Loop, daemon=True,
                                      name="serving-loop")
      self._thread.start()
    return self

  def Stop(self, drain: bool = True, timeout: float = 60.0):
    """drain=True finishes in-flight + queued work first. drain=False
    cancels what is open, but not before the steps already dispatched have
    been retired: a token the device computed reaches its client. The
    loop's thread does both (_CancelOpen), between two iterations."""
    with self._lock:
      if not self._running:
        return
      if not drain:
        self._cancel_open = True
        if self._thread is None or not self._thread.is_alive():
          self._CancelOpen()   # the loop died: nobody else will
      self._work.notify_all()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
      with self._lock:
        if not self._cancel_open and not self.sched.HasWork():
          self._running = False
          self._work.notify_all()
          break
      time.sleep(0.005)
    else:
      with self._lock:
        self._running = self._cancel_open = False
        self._work.notify_all()
    if self._thread is not None:
      self._thread.join(timeout=timeout)
      self._thread = None
    if self.status_server is not None:
      self.status_server.Stop()
      self.status_server = None
    if self.watchdog is not None:
      self.watchdog.Close()   # drop any still-armed flight recorder

  def Submit(self, prompt, max_new_tokens: Optional[int] = None,
             eos_id=_END, seed: Optional[int] = None,
             spec_k: Optional[int] = None,
             spec_w: Optional[int] = None,
             priority: int = 0, tenant=None) -> StreamHandle:
    """Queues a request; returns its streaming handle immediately.

    seed: per-request sampling seed (defaults to the request id) — only
    observable at temperature > 0; same seed = same continuation.
    spec_k: per-request speculative-decoding knob — None defers to the
    engine (full draft length when a draft source is configured, plain
    decode otherwise), 0 opts out, n > 0 caps the draft length
    at min(n, engine k).
    spec_w: per-request tree-speculation WIDTH knob — None defers to the
    engine's draft width, 1 forces a linear chain (exact chain-spec
    behavior), n > 1 caps the branch count at min(n, engine w).
    priority: SLO class, higher = more urgent — consulted only under
    scheduler_mode='priority' (admission order + preemption rights);
    FIFO engines ignore it. tenant: quota/fairness label; a tenant over
    its token-rate quota gets QuotaExceeded here, before any handle or
    scheduler state is created."""
    max_new = max_new_tokens or self.default_max_new
    eos = self.eos_id if eos_id is _END else eos_id
    with self._lock:
      self._seq_counter += 1
      req_id = self._seq_counter
      req = scheduler_lib.Request(req_id, prompt, max_new, eos, seed=seed,
                                  spec_k=spec_k, spec_w=spec_w,
                                  priority=priority, tenant=tenant)
      total = len(req.prompt) + req.max_new
      needs = (self.alloc.PagesFor(total) if self._kind_pages is None
               else self._kind_pages.Footprint(total))
      if self.sched.needs_kv_pages and needs > self.alloc.num_pages:
        raise ValueError(
            f"request needs {needs} pages; the pool "
            f"only has {self.alloc.num_pages} — it could never be admitted")
      self.sched.Submit(req)
      handle = StreamHandle(req_id, self, time.perf_counter())
      self._handles[req_id] = handle
      if self.trace is not None:
        self.trace.Submit(req_id, len(req.prompt), req.max_new)
      if self.watchdog is not None:
        st = self.sched.Stats()
        self.watchdog.ObserveQueue(st["queue_depth"], st["finished"])
      self._work.notify_all()
    return handle

  def Cancel(self, req_id) -> bool:
    with self._lock:
      ok = self.sched.Cancel(req_id)
      if ok:
        h = self._handles.get(req_id)
        if h is not None and not h.done:
          h._Finish("cancelled")
        if self.trace is not None:
          self.trace.Retire(req_id, "cancelled",
                            self._pages_of.pop(req_id, 0))
      return ok

  def _Loop(self):
    while True:
      with self._lock:
        if not self._running:
          return
        cancel_open = self._cancel_open
        if not cancel_open and not self.sched.HasWork():
          self._work.wait(timeout=0.05)
          # no work is not a stall: refresh liveness so an idle replica
          # keeps answering /healthz 200 past the no_heartbeat window
          if self.watchdog is not None:
            self.watchdog.Idle()
          continue
      if cancel_open:
        self._CancelOpen()
      else:
        self.StepOnce()

  # -- core step (shared by sync and async modes) ----------------------------

  def _AdmitPhase(self):
    """Evict + admit + per-admission bookkeeping (caller holds the lock)."""
    self.sched.EvictCancelled()
    admitted = self.sched.Admit()
    for seq in admitted:
      h = self._handles.get(seq.id)
      # a restored PREEMPTED sequence comes back through Admit too:
      # admit_time (and the prefix-hit count) belong to its FIRST
      # admission only
      first = h is None or h.admit_time is None
      if h is not None and h.admit_time is None:
        h.admit_time = time.perf_counter()
      pages = 0
      if self.sched.needs_kv_pages:
        try:
          pages = len(self.alloc.PagesOf(seq.id))
        except KeyError:
          pages = 0
      self._pages_of[seq.id] = pages
      if seq.reused_tokens > 0 and first:
        self._counters["prefix_hit_tokens"].Inc(seq.reused_tokens)
        if self.trace is not None:
          self.trace.PrefixHit(seq.id, seq.reused_tokens)
      if self.trace is not None:
        self.trace.Admit(seq.id, seq.slot, pages)
    if self.prefix_cache is not None and admitted:
      # split shared pages the new rows will write into BEFORE any step
      self._RunCow(admitted)

  def StepOnce(self) -> int:
    """One iteration of the loop: admit, build and dispatch the next step,
    then fetch and commit the oldest one in flight; returns #events.

    Every step — any mix of prefill chunks, plain decode rows, and
    spec-verify rows — launches the ONE compiled packed-token program;
    with a draft source, rows that speculate get a draft pass first
    while prefilling neighbors ride the same step.

    The loop is a pipeline: step n+1 is dispatched BEFORE step n's tokens
    are fetched, so the device runs step n while the host builds and
    places step n+1, and never waits out the host's turn. What step n+1
    needs of step n it gets without the host: the cursors moved when step
    n was dispatched (Scheduler.AdvanceRaggedStep), and the tokens it fed
    back are gathered from step n's draws on the device (_FeedTokens). So
    the `device_wait` and `commit` of an iteration belong to the step
    before the one its `build`, `h2d` and `dispatch` belong to. A draft
    source reads the committed token on the host, so an engine with one
    leaves nothing in flight between iterations: the same loop at depth
    one, which is the serial order. An iteration that finds nothing to
    build retires what is in flight."""
    spans = self._spans
    if self._first_steps is None:
      self._first_steps = _STARTUP.OpenPhase("first_steps")
    spans.Begin()
    try:
      spans.To("lock_wait")
      with self._lock:
        spans.To("admit")
        self._AdmitPhase()
        spans.To("build")
        spec_k = self.spec.k if self.spec is not None else 0
        spec_w = self.spec.w if self.spec is not None else 1
        batch = self.sched.BuildRaggedStep(self._ragged_t, self._ragged_wmax,
                                           spec_k=spec_k, spec_w=spec_w)
        if batch is not None:
          tables = np.array(self.sched.block_tables)  # freeze under the lock
          if self._kind_pages is not None:
            tables = np.array(self._kind_pages.tables)   # a layer of the block
          self._NoteDispatch(batch)
      if batch is None:
        # nothing to launch (no record): the pipeline drains
        return len(self._RetireOldest()) if self._in_flight else 0
      self._Dispatch(batch, tables)
      # steps left in flight when the iteration ends: one, or none where a
      # draft source has to see this step's tokens before the next build
      keep = 0 if self.spec is not None else 1
      events = self._RetireOldest() if len(self._in_flight) > keep else ()
      spans.End(self._counters["steps"].value,
                int(batch.rows_desc.row_len.sum()), batch.prompt_tokens,
                sum(r is not None for r in batch.rows),
                self._StepCounters())
      return len(events)
    finally:
      spans.Abandon()   # a no-op after the step's End

  def _StepCounters(self):
    """What a step's record carries of the cumulative counters whose readers
    want them between two steps: expert load as of the newest RETIRED step
    (one behind the record's own), window pages, and as of this step's dispatch
    the hybrid stack's tokens and what the stack's `StepCounts` asks."""
    out = {}
    if self._moe_layers is not None:
      out.update((k, self._counters[k].value) for k in (
          "moe_tokens_routed", "moe_expert_load_max", "moe_expert_load_mean",
          "moe_experts_active", "moe_pairs_elsewhere"))
    if self._kind_pages is not None:
      out["window_pages_released"] = self._kind_pages.pages_released
      out["window_pages_allocated"] = self._kind_pages.pages_allocated
    if self.state_pool is not None:
      out.update((k, self._counters[k].value) for k in (
          "ssm_tokens", "cross_tokens_unread"))
    out.update((k, self._counters[k].value) for k in self._record_counts)
    return out or None

  def _NoteDispatch(self, batch):
    """What is known of a step when it is built (caller holds the lock):
    the scheduler's cursors pass it, and the counters that need no token
    count it."""
    desc = batch.rows_desc
    row_len = np.asarray(desc.row_len, np.int64)
    tokens = int(row_len.sum())
    if self.state_pool is not None:
      self._counters["ssm_tokens"].Inc(tokens)
      # of a prefill row's tokens only the prompt's last one is sampled from
      # (before the cursors advance: prompt_remaining is as the step finds it)
      self._counters["cross_tokens_unread"].Inc(sum(
          int(n) - (seq.prompt_remaining <= n)
          for seq, n in zip(batch.rows, row_len)
          if seq is not None and n > 0
          and seq.state is scheduler_lib.SeqState.PREFILL))
    for counters, count in self._step_counts:   # each by its owner's function
      for counter, n in zip(counters, count(desc.row_q_pos, row_len)):
        counter.Inc(n)
    if self.trace is not None and batch.mixed:
      # emit prefill-chunk spans BEFORE the cursors advance
      for i, seq in enumerate(batch.rows):
        n = int(desc.row_len[i])
        if (seq is not None
            and seq.state is scheduler_lib.SeqState.PREFILL and n > 0):
          self.trace.PrefillChunk(seq.id, n)
    self.sched.AdvanceRaggedStep(batch)
    self._counters["steps"].Inc()
    if self._in_flight:
      self._counters["steps_overlapped"].Inc()
    self._counters["mixed_steps" if batch.mixed else "decode_steps"].Inc()
    if 0 < self._narrow_rows and tokens <= self._narrow_rows:
      self._counters["narrow_steps"].Inc()
    self._counters["prompt_tokens"].Inc(batch.prompt_tokens)
    if self.paged_path == "dense":
      self._counters["dense_fallback_steps"].Inc()
    if self._kv_quantized:
      self._counters["quantized_steps"].Inc()
    window = self._profile_window
    if window is not None:
      window.Start()

  def _Dispatch(self, batch, tables):
    """Places one built step's arguments and launches it; its results stay
    on the device, at the tail of `_in_flight`."""
    spans = self._spans
    desc = batch.rows_desc
    q_logits = None
    if self.spec is not None:
      spans.To("draft")
      if batch.any_spec:
        # draft outside the lock (device work); the batch's row-level
        # view has in_len > 0 only on drafting rows, so prefill rows ride
        # the step without activating the draft pass
        d_toks, q_logits = self.spec.Draft(self._theta, self._states,
                                           batch, tables)
        # one dtype for both the drafted and the no-draft (zeros) case:
        # the verify program must keep a single compiled signature
        q_logits = q_logits.astype(jnp.float32)
        # tree rows pack branch-major: branch bi's depth-d node sits at
        # packed column 1 + bi*rk + d but draft index bi*spec_k + d —
        # clamped rows (rk < spec_k) keep only each branch's prefix
        spec_k = self.spec.k
        for i in range(self.max_batch):
          rk = int(batch.row_k[i])
          if rk > 0:
            for bi in range(int(batch.row_w[i])):
              batch.tok_ids[desc.row_cols[i, 1 + bi * rk:1 + (bi + 1) * rk]
                            ] = d_toks[i, bi * spec_k:bi * spec_k + rk]
      else:
        q_logits = self._ZeroQLogits()
    spans.To("h2d")
    rows_dev = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in desc))
    tok_ids = jnp.asarray(batch.tok_ids)
    args = [rows_dev, jnp.asarray(tables), jnp.asarray(batch.row_seeds),
            jnp.asarray(batch.row_pos)]
    if self.spec is not None:
      args += [jnp.asarray(batch.row_k)]
      if self.spec.w > 1:
        args += [jnp.asarray(batch.row_w)]
      args += [q_logits]
    spans.To("dispatch")
    if batch.feeds:
      # the tokens this step feeds back that the host has not seen: they
      # are draws of the step before, the only one that can be in flight
      # while a step is built, and reach this step's tok_ids on the device
      tok_ids = self._compile_log.Call(
          "feed", self._feed_fn, self._in_flight[-1][1], tok_ids)
    # (sampled, new_states), with (out, alen) between them under a draft source
    *drawn, new_states = self._compile_log.Call(
        "ragged", self._ragged_fn, self._theta, self._states, tok_ids, *args)
    # the newest decode state, a future: whatever reads or rewrites it from
    # here on (the next step, a CoW copy, a spill's gather, a restore's
    # scatter, a prefix export) is run by the device behind this step
    self._states = new_states
    self._in_flight.append((batch, *drawn))

  def _RetireOldest(self) -> list:
    """Fetches the oldest dispatched step's tokens and commits them:
    the half of the commit that needs the values. Returns the events."""
    spans = self._spans
    batch, *drawn = self._in_flight.popleft()
    spans.To("device_wait")
    drawn = [np.asarray(x) for x in drawn]   # blocks until the step is done
    routed = None
    if self.spec is None and len(drawn) == 2:
      routed = drawn.pop()                   # [layers, experts]
    sampled, out, alen = drawn if len(drawn) == 3 else (drawn[0], None, None)
    spans.To("lock_wait")
    with self._lock:
      spans.To("commit")
      events = self.sched.CommitRaggedStep(batch, sampled, out, alen)
      if routed is not None:
        if self._moe_shares:
          routed, elsewhere = routed[:, :-1], routed[:, -1]
          self._counters["moe_pairs_elsewhere"].Inc(int(elsewhere.sum()))
        self._counters["moe_tokens_routed"].Inc(int(routed.sum()))
        self._counters["moe_expert_load_max"].Inc(int(routed.max(-1).sum()))
        self._counters["moe_expert_load_mean"].Inc(
            float(routed.mean(-1).sum()))
        self._counters["moe_experts_active"].Inc(int((routed > 0).sum()))
      if batch.dropped:
        self._counters["inflight_rows_dropped"].Inc(batch.dropped)
      if batch.any_spec:
        self._counters["spec_cycles"].Inc()
        if batch.width_clamps:
          self._counters["spec_width_clamps"].Inc(batch.width_clamps)
        for i, seq in enumerate(batch.rows):
          rk = int(batch.row_k[i])
          if (seq is None or rk == 0
              or seq.state is scheduler_lib.SeqState.CANCELLED):
            continue
          rw = int(batch.row_w[i])
          m = min(int(alen[i]), rk)
          self._counters["draft_tokens"].Inc(rw * rk)
          self._counters["accepted_tokens"].Inc(m)
          self._counters["spec_branches"].Inc(rw)
          self.spec.accepted_len_hist[m] += 1
          if self.trace is not None:
            self.trace.SpecVerify(seq.id, rw * rk, m)
            if rw * rk - m > 0:
              self.trace.Rollback(seq.id, rw * rk - m)
      self._PushEvents(events)
      self._TickProfile()
      self._BeatWatchdog()
    if events and self._first_steps:
      self._first_steps.Close()
      self._first_steps = False
    return events

  def _CancelOpen(self):
    """Stop(drain=False)'s work, on the thread that drives the loop (which
    alone may retire a step): FIRST the steps already dispatched are
    retired — their tokens were computed, and the clients get them — and
    only then is every request still open cancelled."""
    spans = self._spans
    while self._in_flight:
      spans.Begin()
      try:
        self._RetireOldest()
      finally:
        spans.Abandon()
    with self._lock:
      for h in list(self._handles.values()):
        if not h.done:
          self.Cancel(h.id)   # RLock: reentrant under self._lock
      self._cancel_open = False

  def _PushEvents(self, events):
    """Streams committed tokens to their handles (caller holds the lock)."""
    for req_id, tok, finished in events:
      self._counters["tokens_emitted"].Inc()
      if self.trace is not None:
        self.trace.Token(req_id)
      h = self._handles.get(req_id)
      if h is None:
        if finished and self.trace is not None:
          self.trace.Retire(req_id, self.sched._by_id[req_id].finish_reason,
                            self._pages_of.pop(req_id, 0))
        continue
      h._Push(tok)
      if finished:
        h._Finish(self.sched._by_id[req_id].finish_reason)
        if self.trace is not None:
          self.trace.Retire(req_id, h.finish_reason,
                            self._pages_of.pop(req_id, 0))
        self._ObserveLatencies(h)

  def _ObserveLatencies(self, h: StreamHandle):
    """Fills the latency histograms from the handle's lifecycle times;
    independent of whether tracing is on (caller holds the lock)."""
    if h.admit_time is not None:
      self._h_queue_wait.Observe(h.admit_time - h.submit_time)
      # per-SLO-class queue-wait histograms (priority mode): lazily
      # created per class actually seen, so fifo engines publish none
      if self.scheduler_mode == "priority":
        seq = self.sched._by_id.get(h.id)
        cls = seq.req.priority if seq is not None else 0
        hist = self._h_queue_wait_cls.get(cls)
        if hist is None:
          hist = self.metrics.Histogram(f"serving/queue_wait_s_c{cls}")
          self._h_queue_wait_cls[cls] = hist
        hist.Observe(h.admit_time - h.submit_time)
    if h.first_token_time is not None:
      self._h_ttft.Observe(h.first_token_time - h.submit_time)
      ntok = len(h._tokens)
      if ntok > 1 and h.finish_time is not None:
        self._h_tpot.Observe(
            (h.finish_time - h.first_token_time) / (ntok - 1))

  def _TickProfile(self):
    """Advances an armed N-step ProfileWindow (caller holds the lock)."""
    if self._profile_window is not None:
      if self._profile_window.StepDone():
        self._profile_window = None

  def _BeatWatchdog(self):
    """One step's liveness heartbeat + queue observation (caller holds
    the lock). The watchdog's own lock nests strictly inside the engine
    lock here; Check() runs lock-free of the engine on scrape threads."""
    if self.watchdog is not None:
      st = self.sched.Stats()
      self.watchdog.ObserveQueue(st["queue_depth"], st["finished"])
      self.watchdog.Beat()

  def ProfileSteps(self, logdir: str, steps: int = 5):
    """Arms a jax.profiler window covering the next `steps` engine steps;
    the trace lands under `<logdir>/plugins/profile/` (no-op on backends
    without profiler support). Returns the armed ProfileWindow."""
    window = observe.ProfileWindow(logdir, steps=steps)
    with self._lock:
      self._profile_window = window
    return window

  # -- sync GShardDecode-parity mode ----------------------------------------

  def RunBatch(self, prompts: np.ndarray, prompt_lens: np.ndarray,
               max_new_tokens: Optional[int] = None) -> np.ndarray:
    """Decodes a fixed prompt set inline; returns [B, max_new] int32.

    The continuous-batching twin of `GShardDecode.DecodeOnce`: same greedy
    sampling, token-identical outputs (asserted in tests), but sequences
    retire individually so the pool drains as rows finish. eos is ignored
    here (GShardDecode always decodes exactly max_decode_steps tokens)."""
    assert self._thread is None, "RunBatch drives the loop inline; Stop() first"
    prompts = np.asarray(prompts)
    max_new = max_new_tokens or self.default_max_new
    handles = []
    for i in range(prompts.shape[0]):
      ln = int(prompt_lens[i])
      handles.append(self.Submit(prompts[i, :ln], max_new, eos_id=None))
    while True:
      with self._lock:
        if not self.sched.HasWork():
          break
      self.StepOnce()
    out = np.zeros((prompts.shape[0], max_new), np.int32)
    for i, h in enumerate(handles):
      toks = h.Result(timeout=0)
      out[i, :len(toks)] = toks
    return out

  # -- introspection ---------------------------------------------------------

  def Stats(self) -> dict:
    """Atomic engine snapshot (the consistent read surface; the registry's
    Snapshot() is the lock-free best-effort view). Key set is declared in
    observe/schema.py and validated by ValidateEngineStats in tests."""
    with self._lock:
      stats = {k: c.value for k, c in self._counters.items()}
      stats["paged_path"] = self.paged_path
      stats["kv_cache_dtype"] = self.kv_cache_dtype
      stats["kv_bytes_per_token"] = self.kv_bytes_per_token
      stats["serve_int8_weights"] = self.serve_int8_weights
      stats["head_rows"] = self.head_rows
      stats["attend_calls"] = self.attend_calls
      stats["attend_plans"] = self.attend_plans
      stats["scheduler"] = self.sched.Stats()
      stats["kv_pages"] = (self._kind_pages or self.alloc).Stats()
      stats["mixers"] = dict(self.mixers)
      if hasattr(self._task.stack, "LayerKinds"):
        stats["layer_kinds"] = self._task.stack.LayerKinds()
      stats["prefix_cache"] = (
          self.prefix_cache.Stats() if self.prefix_cache is not None
          else observe_schema.DisabledPrefixCacheStats())
      if self.state_pool is not None:
        stats["state_slots"] = self.state_pool.Stats()
        stats["shared_kv_read_layers"] = self._shared_kv_read_layers
      # acceptance telemetry: hist[m] = verify rows whose accepted draft
      # prefix had length m ([] for engines without a draft source).
      # accepted_depth_hist is the tree-speculation reading of the SAME
      # data — m is the accepted root-to-leaf DEPTH along the winning
      # branch (chains: depth == prefix length, so the views coincide).
      stats["accepted_len_hist"] = (
          self.spec.accepted_len_hist.tolist() if self.spec else [])
      stats["accepted_depth_hist"] = (
          self.spec.accepted_len_hist.tolist() if self.spec else [])
      if self.spec is not None:
        stats["spec"] = self.spec.Describe()
      if self.trace is not None:
        stats["trace"] = self.trace.Stats()
      if self.watchdog is not None:
        stats["watchdog"] = self.watchdog.Stats()
      records = self._compile_log.Records()
      # compiled-step-program census: how many distinct per-step programs
      # this engine has actually compiled (exactly 1 across any admit/
      # decode/spec/retire mix). Draft programs are NOT step programs.
      records[observe_schema.COMPILE_CENSUS_KEY] = sum(
          1 for n in records if n in observe_schema.STEP_PROGRAM_NAMES)
      stats["compile"] = records
    return stats
