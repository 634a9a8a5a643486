"""Continuous-batching request scheduler.

Owns the host-side serving state machine: a FIFO of waiting requests, a
fixed array of B decode slots, and the page allocator. Each engine
iteration is admit → build → (device step) → commit:

- `Admit` moves queued requests into free slots while the allocator can
  reserve their WHOLE worst-case footprint (ceil((prompt + max_new) /
  page_size) pages) up front. Reserve-all-on-admission means an admitted
  sequence can never run out of pages mid-flight, so there is no
  preemption/swap machinery — pool pressure shows up only as queueing
  (the allocator-exhaustion satellite: graceful, never a crash). With a
  prefix cache attached (serving/prefix_cache.py), admission first
  probes the cache: matched full-page prefixes are BORROWED (refcount
  shares, not fresh pages), only the uncached remainder is charged to
  the pool — so shared pages stop counting against the reservation,
  which is the concurrency jump — and prefill starts at the first
  uncached token. A match covering the whole prompt copy-on-writes its
  final page, because prefill must recompute the last prompt token.
- `BuildRaggedStep` packs the live slots onto one static [T] token axis
  for the compiled RaggedStep program (core/ragged.py). Decode rows are
  mandatory and packed first (the last sampled token, plus draft slots
  when the row speculates); prefilling rows then share what is left of
  the axis, so a step that carries prompt tokens is a MIXED step and
  decode is never stalled behind prefill.
- A step's commit is split along what the host knows when, so that the
  engine can build step n+1 while step n's tokens are still on the device:
  `AdvanceRaggedStep` (at dispatch, no token values) advances prompt and
  decode cursors, turns finished prefills into decoders, counts the output
  position each row's draw will fill (`Sequence.n_out`), and retires rows
  that end BY LENGTH, freeing their slot + pages at once so `Admit` can
  refill the slot on the very next iteration; `CommitRaggedStep` (with the
  tokens) appends each draw to its sequence, finds EOS, and returns the
  events to stream (a speculating row's accepted prefix plus its
  correction token, rolling the cursor back over the rejected tail: such a
  row's cursor follows the verify result, so all of its commit happens
  here). A decode row built while its newest token is still undelivered
  carries, in place of the token, the column of the previous step's draws
  that holds it (`RaggedBatch.tok_ids`), and the engine resolves it on the
  device. `CommitRaggedStep` on a batch that was never advanced does both
  halves, which is the whole commit of a loop that keeps one step in
  flight.

SLO-aware scheduling (`scheduler_mode='priority'`, opt-in; 'fifo' is the
bit-exact legacy default): requests carry a `priority` class and a
`tenant` label. Admission serves the highest priority class first;
within a class, preempted work resumes before fresh work, and fresh
admissions are weighted-fair across tenants (least admitted-token
service per unit weight goes first). Under pool pressure a strictly
higher-priority arrival PREEMPTS a victim — lowest priority first,
fewest generated tokens first — by spilling its private KV pages and
O(1)-mixer state row to a host tier (`kv_cache.HostPageStore`) and
parking it in a PREEMPTED queue; re-admission restores the saved bytes
into fresh pages at the same logical slots and resumes from the spilled
cursor, no recompute. The device halves (page gather/scatter, state row
gather/scatter) are injected by the engine as `spill_fn`/`restore_fn` /
`state_spill_fn`/`state_restore_fn` callbacks, so the scheduler itself
stays device-free. Per-tenant token-rate quotas (`TokenBucket`) gate
`Submit`, raising `QuotaExceeded` before any state is created.

Sequences/requests are identified by the user-visible request id. The
scheduler is deliberately device-free (pure Python + numpy) so its
lifecycle is unit-testable with fabricated sample arrays.
"""

from __future__ import annotations

import collections
import enum
import time
from typing import Optional

import numpy as np

from lingvo_tpu.core import ragged
from lingvo_tpu.serving import kv_cache


class SeqState(enum.Enum):
  QUEUED = "queued"
  PREFILL = "prefill"
  DECODE = "decode"
  FINISHED = "finished"
  CANCELLED = "cancelled"
  PREEMPTED = "preempted"


class QuotaExceeded(Exception):
  """Raised by Submit when the tenant's token-rate bucket is empty."""


class TokenBucket:
  """Per-tenant token-rate quota: `rate` tokens/sec up to `burst` deep.

  A request is charged its whole worst-case footprint (prompt + max_new)
  at Submit — the same unit admission reserves pages for — so a tenant
  cannot laundromat quota by submitting long generations cheaply.
  clock: injectable monotonic-seconds source (tests)."""

  def __init__(self, rate: float, burst: float, clock=None):
    assert rate >= 0 and burst > 0, (rate, burst)
    self.rate = float(rate)
    self.burst = float(burst)
    self._clock = clock if clock is not None else time.monotonic
    self._level = float(burst)
    self._last = self._clock()

  def _Refill(self):
    now = self._clock()
    self._level = min(self.burst,
                      self._level + (now - self._last) * self.rate)
    self._last = now

  def TryTake(self, n: float) -> bool:
    """Charges n tokens if the bucket covers them; False otherwise."""
    self._Refill()
    if n <= self._level:
      self._level -= n
      return True
    return False

  @property
  def level(self) -> float:
    self._Refill()
    return self._level


class Request:
  """One user request: prompt ids + generation budget.

  seed: per-request sampling seed (core/sampling.py row stream). Defaults
  to the request id for int ids, so every request has a replayable stream
  even when the caller doesn't pick one: resubmitting with the same seed
  under the same checkpoint yields the same continuation regardless of
  which slot or batch neighbors it is scheduled with.

  spec_k: per-request speculative-decoding knob. None (default) defers to
  the engine — full draft length k when the engine speculates, the plain
  single-token row otherwise. 0 opts this request out of
  speculation entirely; n > 0 caps its draft length at min(n, engine k).
  Only consulted by engines with a draft source configured.

  spec_w: per-request TREE-speculation width knob — the number of
  branches the draft tree forks into at depth 1 (core/ragged.py tree
  contract). None (default) defers to the engine's draft width, 1 forces
  a linear chain (the exact PR-11 behavior), n > 1 caps the width at
  min(n, engine w). Only consulted when the engine's draft source has
  width > 1.

  priority: SLO class, higher = more urgent (default 0). Consulted only
  by `scheduler_mode='priority'` schedulers: admission serves higher
  classes first, and a strictly higher-priority arrival may preempt a
  lower one under pool pressure. FIFO schedulers ignore it.

  tenant: opaque tenant label for quota + fairness accounting (None =
  the anonymous tenant). Weighted-fair admission within a priority
  class and per-tenant token-rate quotas key on it.
  """

  def __init__(self, req_id, prompt, max_new_tokens: int,
               eos_id: Optional[int] = None, seed: Optional[int] = None,
               spec_k: Optional[int] = None, spec_w: Optional[int] = None,
               priority: int = 0, tenant=None):
    prompt = [int(t) for t in prompt]
    assert len(prompt) >= 1, "empty prompt"
    assert max_new_tokens >= 1, max_new_tokens
    assert spec_k is None or spec_k >= 0, spec_k
    assert spec_w is None or spec_w >= 1, spec_w
    self.id = req_id
    self.prompt = prompt
    self.max_new = int(max_new_tokens)
    self.eos_id = eos_id
    self.spec_k = spec_k
    self.spec_w = spec_w
    self.priority = int(priority)
    self.tenant = tenant
    if seed is None:
      seed = req_id if isinstance(req_id, int) else abs(hash(req_id))
    self.seed = int(seed) % (2**31)


class Sequence:
  """A request's in-flight decode state (slot-resident)."""

  def __init__(self, request: Request):
    self.req = request
    self.state = SeqState.QUEUED
    self.pos = 0          # tokens WRITTEN to the KV cache so far
    self.out = []         # generated tokens (out[-1] may not be cached yet)
    # draws dispatched and not delivered yet: one per step in flight that
    # this sequence draws a token in. A count, never a placeholder in `out`
    # that a client could see. A sequence that ends early (eos, cancel)
    # sets it to 0: the draws still in flight are void and will be dropped.
    self.pending = 0
    # where the newest such draw is: its column in its step's sampled [T]
    self.src_col = -1
    self.finish_reason = None
    self.slot = None      # decode slot index, set at admission (telemetry)
    # committed tokens an independent draft model's recurrent state has
    # consumed so far (speculative decoding only; engine-maintained)
    self.draft_pos = 0
    # prefix-cache admission results: prompt tokens whose prefill was
    # skipped (seq.pos starts there), and (src, dst) physical page pairs
    # the engine must copy device-side before this sequence's first step
    self.reused_tokens = 0
    self.cow_pairs: list[tuple[int, int]] = []
    # submission order within the scheduler (priority-mode tie-break)
    self.arrival = 0

  @property
  def id(self):
    return self.req.id

  @property
  def prompt_remaining(self) -> int:
    return len(self.req.prompt) - self.pos

  @property
  def n_out(self) -> int:
    """Output positions dispatched so far: what the sampling stream and
    finish-by-length go by."""
    return len(self.out) + self.pending


class RaggedBatch:
  """One packed ragged device step (numpy; the engine jits over it).

  A decode row carries 1 + row_w * row_k tokens (row_k > 0 is the
  spec-verify lane; a row_w > 1 row packs a token TREE of row_w
  branches, each a chain of row_k drafts, in DFS order — core/ragged.py),
  a prefill row a token-budgeted chunk, and every composition launches
  through the SAME compiled program. `rows_desc` is the core/ragged.RaggedRows routing
  pytree; `tok_ids` is the matching packed [T] token stream — draft
  columns hold 0 until the engine fills proposals: branch bi's depth-d
  node at rows_desc.row_cols[i, 1 + bi * row_k[i] + d]. A NEGATIVE entry
  -1 - j stands for a token the host does not have yet: the draw in column
  j of the previous step's sampled [T], which the engine gathers into place
  on the device (`feeds` counts such entries).

  `out_col` ([B], filled by Scheduler.AdvanceRaggedStep): the sampled
  column that holds row i's draw of this step; -1 where the row draws no
  token (mid-prompt, no budget, or a speculating row, whose tokens are the
  verify lane's). `dropped` (set by CommitRaggedStep): rows that were
  computed for a sequence that had ended by the time the tokens arrived.

  The row-level view (ids / q_pos / in_len / rows / row_seeds / row_pos
  / row_k) is what spec_decode.SpecRunner.Draft reads: [B]-shaped, one
  entry per slot. in_len is nonzero ONLY for rows that draft this step,
  so the draft pass activates exactly those — prefill rows ride the same
  device step without drafting, which is what lets spec cycles proceed
  while admissions are still prefilling. row_seeds and row_pos (per-
  request seed, tokens generated so far) make each draw a pure function
  of (engine seed, request seed, output position), never of scheduling.
  """

  def __init__(self, tok_ids, rows_desc: ragged.RaggedRows, rows,
               mixed: bool, prompt_tokens: int, row_seeds, row_pos,
               row_k, any_spec: bool, ids0, row_w=None,
               width_clamps: int = 0):
    self.tok_ids = tok_ids        # [T] int32 packed token stream
    self.feeds = int((tok_ids < 0).sum())   # tokens to gather on the device
    self.advanced = False         # AdvanceRaggedStep ran on this batch
    self.out_col = np.full((len(rows),), -1, np.int64)
    self.dropped = 0
    self.rows_desc = rows_desc    # core/ragged.RaggedRows (numpy members)
    self.rows = rows              # slot -> Sequence or None, frozen at build
    self.mixed = mixed            # True if any prompt token rode this step
    self.prompt_tokens = prompt_tokens
    self.row_seeds = row_seeds    # [B] int32
    self.row_pos = row_pos        # [B] int32
    self.row_k = row_k            # [B] int32 per-branch draft depth this step
    self.any_spec = any_spec      # host fast-path: Draft is skipped if False
    # [B] int32 tree width this step (1 = chain; row_w * row_k draft slots)
    self.row_w = (row_w if row_w is not None
                  else np.ones_like(np.asarray(row_k)))
    self.width_clamps = width_clamps  # rows whose width the pack cap shrank
    # -- row-level view for the draft source ----------------------------
    self.ids = ids0               # [B, 1] int32: column-0 feedback token
    self.q_pos = rows_desc.row_q_pos
    self.in_len = np.where(row_k > 0, 1, 0).astype(np.int32)


class Scheduler:
  """Admission + step building + commit over B slots and a page pool."""

  def __init__(self, max_slots: int, allocator: kv_cache.PageAllocator,
               table_pages: int, needs_kv_pages: bool = True,
               state_pool: Optional[kv_cache.StateSlotPool] = None,
               prefix_cache=None, scheduler_mode: str = "fifo",
               host_store: Optional[kv_cache.HostPageStore] = None,
               tenant_quotas=None, tenant_weights=None, clock=None,
               kind_pages: Optional[kv_cache.KindPages] = None):
    """table_pages: block-table width (pages per sequence) — the static
    max_seq_len / page_size bound every compiled program carries.
    needs_kv_pages: False for pure-O(1)-mixer stacks (no attention layer
    writes the paged pool) — admission is then bounded by slots only, and
    the allocator is never charged. state_pool: slot-ownership accounting
    for O(1) mixer states (acquired on admit, released on retirement).
    prefix_cache: optional serving/prefix_cache.PrefixCache bound to
    `allocator` — admission probes/borrows cached prefix pages and
    completed prefills insert theirs; None keeps the exact legacy path.
    scheduler_mode: 'fifo' (default, the bit-exact legacy admission
    path) or 'priority' (SLO classes + weighted-fair tenants +
    preemption by page spill — module docstring). host_store: the host
    tier preempted pages spill to (priority mode builds one when None).
    tenant_quotas: {tenant: TokenBucket | (rate, burst)} token-rate
    quotas enforced at Submit. tenant_weights: {tenant: weight} for
    weighted-fair admission within a priority class (default 1.0).
    clock: injectable monotonic-seconds source for quota refill (tests).
    kind_pages: the pages of a stack that mixes full and sliding-window
    attention layers (kv_cache.KindPages, over `allocator`): it holds a
    block table a layer of the block in place of `block_tables`, admission
    reserves a request's whole footprint through it, and a row lets go of
    the pages behind its window when its cursor moves (AdvanceRaggedStep).
    FIFO admission only: the engine refuses the configurations whose
    paths know one block table a sequence.
    """
    assert max_slots >= 1 and table_pages >= 1
    assert scheduler_mode in ("fifo", "priority"), scheduler_mode
    self.max_slots = max_slots
    self.alloc = allocator
    self.table_pages = table_pages
    self.needs_kv_pages = needs_kv_pages
    self.state_pool = state_pool
    self.prefix_cache = prefix_cache
    self.scheduler_mode = scheduler_mode
    self.host_store = host_store
    if self.host_store is None and scheduler_mode == "priority":
      self.host_store = kv_cache.HostPageStore()
    # device halves of spill/restore, injected by the owning engine
    # (None on device-free schedulers: spills then move no bytes, which
    # is exactly right for unit tests and pageless stacks)
    self.spill_fn = None          # pages -> host blocks (per paged leaf)
    self.restore_fn = None        # (pages, blocks) -> scatters them back
    self.state_spill_fn = None    # slot -> host rows (per slot leaf)
    self.state_restore_fn = None  # (slot, rows) -> scatters them back
    self.allow_preempt = True     # priority WITHOUT spill: sweep arm knob
    self.tenant_weights = dict(tenant_weights or {})
    self.quotas = {}
    for tenant, q in (tenant_quotas or {}).items():
      self.quotas[tenant] = (q if isinstance(q, TokenBucket)
                             else TokenBucket(q[0], q[1], clock=clock))
    self.waiting = collections.deque()        # of Sequence (QUEUED)
    self.preempted = collections.deque()      # of Sequence (PREEMPTED)
    self.slots: list[Optional[Sequence]] = [None] * max_slots
    self._by_id: dict[object, Sequence] = {}
    # block tables as one stable [B, table_pages] array, rewritten on
    # admit/evict only (steady-state decode steps reuse it as-is)
    self.block_tables = np.zeros((max_slots, table_pages), np.int32)
    self.kind_pages = kind_pages
    if kind_pages is not None:
      assert scheduler_mode == "fifo" and prefix_cache is None
    # counters surfaced via engine Stats()
    self.admitted = 0
    self.finished = 0
    self.cancelled = 0
    self.rejected_overlong = 0
    self.slots_live_peak = 0
    # admissions where cached-prefix ordering picked past the FIFO head
    self.prefix_ordered_admissions = 0
    # tree-speculation rows whose branch count the packed-row cap shrank
    self.width_clamps = 0
    # SLO accounting (priority mode; zeros under fifo)
    self.preemptions = 0
    self.restores = 0
    self.quota_rejections = 0
    self._arrival = 0
    self._tenant_service: dict = {}   # tenant -> admitted token footprint
    # steps advanced (dispatched) whose tokens CommitRaggedStep has not
    # delivered yet: work, as far as HasWork's callers are concerned
    self.steps_in_flight = 0

  # -- submission ------------------------------------------------------------

  def Submit(self, request: Request) -> Sequence:
    # the max_seq_len capacity bound holds for pageless stacks too: the
    # compiled step programs still carry table_pages-wide block tables,
    # and q_pos positions beyond the bound were never validated
    total = len(request.prompt) + request.max_new
    if self.alloc.PagesFor(total) > self.table_pages:
      self.rejected_overlong += 1
      raise ValueError(
          f"request {request.id!r} needs {self.alloc.PagesFor(total)} pages "
          f"(prompt {len(request.prompt)} + max_new {request.max_new}) but "
          f"block tables hold {self.table_pages}")
    bucket = self.quotas.get(request.tenant)
    if bucket is not None and not bucket.TryTake(total):
      self.quota_rejections += 1
      raise QuotaExceeded(
          f"tenant {request.tenant!r} over token-rate quota: request "
          f"footprint {total} exceeds bucket level {bucket.level:.0f} "
          f"(rate {bucket.rate}/s, burst {bucket.burst:.0f})")
    seq = Sequence(request)
    self._arrival += 1
    seq.arrival = self._arrival
    self._by_id[request.id] = seq
    self.waiting.append(seq)
    return seq

  def Cancel(self, req_id) -> bool:
    """Marks a request cancelled; resources return at the next boundary."""
    seq = self._by_id.get(req_id)
    if seq is None or seq.state in (SeqState.FINISHED, SeqState.CANCELLED):
      return False
    if seq.state is SeqState.QUEUED:
      try:
        self.waiting.remove(seq)
      except ValueError:
        pass
      self._Retire(seq, SeqState.CANCELLED, "cancelled")
      self.cancelled += 1
      return True
    if seq.state is SeqState.PREEMPTED:
      # parked off-device: drop the host-tier entry, then release the
      # refs it still holds on shared prefix pages (Free skips HOLEs)
      try:
        self.preempted.remove(seq)
      except ValueError:
        pass
      if self.host_store is not None:
        self.host_store.Drop(seq.id)
      self._Retire(seq, SeqState.CANCELLED, "cancelled")
      self.cancelled += 1
      return True
    seq.state = SeqState.CANCELLED   # slot/pages reclaimed by EvictCancelled
    seq.finish_reason = "cancelled"
    seq.pending = 0                  # a draw still in flight is dropped
    return True

  # -- boundary phases -------------------------------------------------------

  def EvictCancelled(self) -> list:
    """Frees slots/pages of mid-flight cancellations. Call before Admit."""
    evicted = []
    for i, seq in enumerate(self.slots):
      if seq is not None and seq.state is SeqState.CANCELLED:
        self.slots[i] = None
        self._FreePages(seq.id)
        if self.state_pool is not None:
          self.state_pool.Release(seq.id)
        self.cancelled += 1
        evicted.append(seq)
    return evicted

  def _AdmitPages(self, seq: Sequence) -> bool:
    """Reserves seq's whole footprint, borrowing cached prefix pages.

    Probes the prefix cache (if any) for the prompt's longest cached
    page-aligned prefix, pins those pages with refcount shares, charges
    the pool only for the uncached remainder, copy-on-writes any shared
    page prefill will write into (only the final matched page, and only
    on a full-cover match), and rewinds seq.pos past the reused tokens.
    Returns False with NO net side effects when the pool cannot cover
    the remainder even after evicting unreferenced cached pages."""
    req = seq.req
    total = self.alloc.PagesFor(len(req.prompt) + req.max_new)
    shared, matched = [], 0
    if self.prefix_cache is not None:
      shared, matched = self.prefix_cache.Probe(req.prompt)
    # prefill resumes at the first uncached token; a full-cover match
    # still recomputes the LAST prompt token (its logits seed decoding)
    p0 = min(matched, len(req.prompt) - 1)
    first_write_page = p0 // self.alloc.page_size
    n_cow = max(len(shared) - first_write_page, 0)
    need_new = (total - len(shared)) + n_cow
    # pin the borrowed pages FIRST (refcount >= 2 makes them un-evictable),
    # then squeeze the pool: cached-but-unreferenced pages yield under
    # admission pressure
    self.alloc.Share(seq.id, shared)
    if not self.alloc.CanAllocate(need_new):
      if self.prefix_cache is not None:
        self.prefix_cache.EvictForPressure(need_new - self.alloc.num_free)
      if not self.alloc.CanAllocate(need_new):
        self.alloc.Free(seq.id)   # undo the share; head-of-line blocks
        return False
    cow = []
    for idx in range(first_write_page, len(shared)):
      pair = self.alloc.CopyOnWrite(seq.id, idx)
      if pair is not None:
        cow.append(pair)
        if self.prefix_cache is not None:
          self.prefix_cache.NoteCow()
    if total > len(shared):
      self.alloc.Allocate(seq.id, total - len(shared))
    if self.prefix_cache is not None:
      self.prefix_cache.NoteAdmitted(req.prompt, matched)
    seq.pos = p0
    seq.reused_tokens = p0
    seq.cow_pairs = cow
    return True

  def _NextWaiting(self) -> int:
    """Index into self.waiting of the next admission candidate.

    Strict FIFO without a prefix cache. With one attached, reorders
    WITHIN the admission head — the first max_slots queued requests —
    preferring the largest cached-prefix match (FIFO breaks ties, so
    all-miss windows degenerate to the legacy order). Admitting the
    best-cached candidate first matters under pool pressure: its shared
    pages get pinned (refcount > 1, un-evictable) before cache-missing
    admissions squeeze the pool and evict them, so the same eviction
    budget yields strictly more reused tokens. The window bound keeps
    starvation no worse than head-of-line blocking: nothing deeper than
    the head window ever jumps the queue, and a passed-over head is
    retried every boundary."""
    if self.prefix_cache is None or len(self.waiting) <= 1:
      return 0
    best, best_hit = 0, -1
    for j, seq in enumerate(self.waiting):
      if j >= self.max_slots:
        break
      hit = self.prefix_cache.PeekHitTokens(seq.req.prompt)
      if hit > best_hit:
        best, best_hit = j, hit
    return best

  def Admit(self) -> list:
    """Admits queued (and, in priority mode, preempted) requests.

    'fifo': the bit-exact legacy path (_AdmitFifo) — FIFO with
    head-window prefix-cache reordering and intentional head-of-line
    blocking. 'priority': highest SLO class first, preempted-before-
    fresh and weighted-fair tenants within a class, preemption by page
    spill under pressure (_AdmitPriority)."""
    if self.scheduler_mode == "priority":
      return self._AdmitPriority()
    return self._AdmitFifo()

  def _AdmitFifo(self) -> list:
    """Admits waiting requests into free slots while pages last.

    FIFO, except that within the head window the largest cached-prefix
    match goes first (_NextWaiting). Head-of-line blocking on the pool
    is intentional: skipping a big request to admit a small one behind
    it would starve the big one — so when the cache-ordered pick fails
    to fit, the true FIFO head still gets its legacy try, and admission
    stops only when that fails too."""
    admitted = []
    for i in range(self.max_slots):
      if self.slots[i] is not None or not self.waiting:
        continue
      if self.kind_pages is not None:
        # a page a layer of the block, by its kind; strict FIFO
        seq = self.waiting[0]
        total = len(seq.req.prompt) + seq.req.max_new
        if not self.kind_pages.CanAdmit(total):
          break
        self.waiting.popleft()
        self.kind_pages.Admit(seq.id, i, total)
        pages = []
      elif self.needs_kv_pages:
        pick = self._NextWaiting()
        seq = self.waiting[pick]
        if not self._AdmitPages(seq):
          if pick == 0:
            break
          pick, seq = 0, self.waiting[0]
          if not self._AdmitPages(seq):
            break
        if pick:
          self.prefix_ordered_admissions += 1
        del self.waiting[pick]
        pages = self.alloc.PagesOf(seq.id)
      else:
        # pure O(1)-mixer stack: nothing pages, a free slot IS admission
        seq = self.waiting.popleft()
        pages = []
      self.slots[i] = seq
      seq.state = SeqState.PREFILL
      seq.slot = i
      self.block_tables[i, :] = 0
      self.block_tables[i, :len(pages)] = pages
      if self.state_pool is not None:
        self.state_pool.Acquire(seq.id, i)
      self.admitted += 1
      self.slots_live_peak = max(
          self.slots_live_peak, sum(s is not None for s in self.slots))
      admitted.append(seq)
    return admitted

  # -- priority admission + preemption (scheduler_mode='priority') -----------

  def _CandidateKey(self, seq: Sequence):
    """Admission order: highest class, then resume-before-fresh, then
    weighted-fair across tenants (least admitted-token service per unit
    weight), then arrival order."""
    service = self._tenant_service.get(seq.req.tenant, 0)
    weight = self.tenant_weights.get(seq.req.tenant, 1.0)
    return (-seq.req.priority,
            0 if seq.state is SeqState.PREEMPTED else 1,
            service / weight, seq.arrival)

  def _NextCandidate(self) -> Optional[Sequence]:
    candidates = list(self.preempted) + list(self.waiting)
    if not candidates:
      return None
    return min(candidates, key=self._CandidateKey)

  def _PickVictim(self, min_priority: int) -> Optional[Sequence]:
    """The live sequence a class-`min_priority` arrival may preempt:
    strictly lower priority only (no same-class thrash), lowest class
    first, least generated tokens first (cheapest progress to park)."""
    live = [s for s in self.slots
            if s is not None and s.req.priority < min_priority
            and s.state in (SeqState.PREFILL, SeqState.DECODE)]
    if not live:
      return None
    return min(live, key=lambda s: (s.req.priority, s.n_out, s.arrival))

  def _Preempt(self, victim: Sequence):
    """Spills `victim` to the host tier and parks it PREEMPTED.

    Only its PRIVATE pages move: the data pages' bytes are gathered
    device→host (spill_fn) BEFORE SpillPrivate returns them to the
    pool; trailing reserved pages hold no data and are just freed.
    Shared prefix pages keep the victim's refcount — they stay device-
    resident and pinned, so the prefix cache's nodes stay valid. The
    O(1)-mixer state row rides along (state_spill_fn); the draft-model
    cursor resets so a restored row replays its committed stream into
    whatever slot it lands in, exactly like a fresh admission.

    A victim whose newest step is still in flight has its cursor past
    that step's K/V write: the engine's spill gather reads the newest
    decode state, so the device runs it behind the step and the write is
    in the bytes that leave. The draw itself reaches `out` when it
    arrives (CommitRaggedStep delivers to a PREEMPTED sequence too)."""
    i = victim.slot
    logical_idxs, blocks = [], None
    if self.needs_kv_pages:
      private = self.alloc.PrivatePages(victim.id, victim.pos)
      if private and self.spill_fn is not None:
        blocks = self.spill_fn([pg for _, pg in private])
      logical_idxs = [li for li, _ in private]
      self.alloc.SpillPrivate(victim.id)
    state_row = None
    if self.state_pool is not None:
      if self.state_spill_fn is not None and victim.pos > 0:
        state_row = self.state_spill_fn(i)
      self.state_pool.Release(victim.id)
    self.host_store.Put(victim.id, logical_idxs, blocks, state_row)
    self.slots[i] = None
    self.block_tables[i, :] = 0
    victim.slot = None
    victim.state = SeqState.PREEMPTED
    victim.draft_pos = 0
    self.preempted.append(victim)
    self.preemptions += 1

  def _ReAdmit(self, seq: Sequence, i: int) -> bool:
    """Restores a PREEMPTED sequence into slot i from its host-tier
    entry: re-backs every spilled logical page with a fresh exclusive
    page (FillHoles, all-or-nothing), scatters the saved bytes into
    exactly the logical slots they left, re-binds a state slot and
    scatters the saved mixer-state row, and resumes from the spilled
    cursor (PREFILL if prompt remains, DECODE otherwise). Returns False
    with no side effects when the pool cannot cover the holes."""
    if self.needs_kv_pages:
      holes = self.alloc.HoleCount(seq.id)
      if not self.alloc.CanAllocate(holes):
        if self.prefix_cache is not None:
          self.prefix_cache.EvictForPressure(holes - self.alloc.num_free)
        if not self.alloc.CanAllocate(holes):
          return False
    entry = self.host_store.Pop(seq.id)
    pages = []
    if self.needs_kv_pages:
      filled = dict(self.alloc.FillHoles(seq.id))
      if entry.blocks is not None and entry.logical_idxs:
        self.restore_fn([filled[li] for li in entry.logical_idxs],
                        entry.blocks)
      pages = self.alloc.PagesOf(seq.id)
    self.slots[i] = seq
    seq.slot = i
    seq.state = (SeqState.PREFILL if seq.prompt_remaining > 0
                 else SeqState.DECODE)
    self.block_tables[i, :] = 0
    self.block_tables[i, :len(pages)] = pages
    if self.state_pool is not None:
      self.state_pool.Acquire(seq.id, i)
      if entry.state_row is not None and self.state_restore_fn is not None:
        self.state_restore_fn(i, entry.state_row)
    self.restores += 1
    return True

  def _TryAdmitInto(self, seq: Sequence, i: int) -> bool:
    """One admission attempt into free slot i — restore for PREEMPTED
    candidates, the normal reserve-whole-footprint path for fresh ones.
    False (no side effects) when pages don't cover it."""
    if seq.state is SeqState.PREEMPTED:
      if not self._ReAdmit(seq, i):
        return False
      self.preempted.remove(seq)
    else:
      if self.needs_kv_pages:
        if not self._AdmitPages(seq):
          return False
        pages = self.alloc.PagesOf(seq.id)
      else:
        pages = []
      self.waiting.remove(seq)
      self.slots[i] = seq
      seq.state = SeqState.PREFILL
      seq.slot = i
      self.block_tables[i, :] = 0
      self.block_tables[i, :len(pages)] = pages
      if self.state_pool is not None:
        self.state_pool.Acquire(seq.id, i)
      tenant = seq.req.tenant
      self._tenant_service[tenant] = (
          self._tenant_service.get(tenant, 0)
          + len(seq.req.prompt) + seq.req.max_new)
      self.admitted += 1
    self.slots_live_peak = max(
        self.slots_live_peak, sum(s is not None for s in self.slots))
    return True

  def _AdmitPriority(self) -> list:
    """Priority admission: repeatedly place the best candidate
    (_CandidateKey) into a free slot; when slots or pages run out and
    the candidate outranks a running sequence, preempt the cheapest
    strictly-lower-priority victim and retry. Admission stops when the
    best candidate neither fits nor outranks anyone — lower-class
    candidates behind it would steal its resources, so head-of-line
    blocking WITHIN a class is kept (starvation-safe), while higher
    classes always jump the line."""
    admitted = []
    while True:
      cand = self._NextCandidate()
      if cand is None:
        break
      free_i = next((i for i, s in enumerate(self.slots) if s is None),
                    None)
      if free_i is not None and self._TryAdmitInto(cand, free_i):
        admitted.append(cand)
        continue
      victim = (self._PickVictim(cand.req.priority)
                if self.allow_preempt else None)
      if victim is None:
        break
      self._Preempt(victim)
    return admitted

  def HasWork(self) -> bool:
    """A live slot, a parked request, or a dispatched step whose tokens
    are still to be delivered."""
    return (any(s is not None for s in self.slots) or bool(self.waiting)
            or bool(self.preempted) or self.steps_in_flight > 0)

  # -- the packed step --------------------------------------------------------

  def BuildRaggedStep(self, t: int, wmax: int, spec_k: int = 0,
                      spec_w: int = 1) -> Optional[RaggedBatch]:
    """Packs every live slot into ONE [T]-token ragged step (None if idle).

    t: packed token width — static, the engine sizes it once as
    max_slots * (1 + spec_w * spec_k) + prefill token budget, so every
    admit / decode / spec / retire mix reuses one compiled program.
    wmax: widest row the program admits (>= 1 + spec_w * spec_k).
    spec_k: engine draft depth (0 = no draft source configured).
    spec_w: engine draft-tree width (1 = chain speculation).

    Decode rows are mandatory and packed first: 1 feedback token plus
    row_w * row_k draft slots. row_k is clamped per request (request
    opt-out/cap, the remaining max_new budget — which also bounds every
    KV write to the pages reserved at admission — and the packed-row
    cap); row_w (tree rows only) is clamped WIDTH BEFORE
    DEPTH under min(wmax, ragged.MAX_TREE_COLS) — under pressure a
    request loses branches before it loses per-branch depth, because a
    deep chain keeps the accepted-length upside that extra siblings only
    hedge. Each clamped row bumps `width_clamps`. A row_w > 1 row packs
    its tree in DFS order (branch bi's depth-d node at column
    1 + bi * row_k + d) and ships parent pointers so
    ragged.BuildRaggedRows emits ancestor masks; row_w == 1 rows stay
    chain-packed — bitwise the pre-tree build. Prefill rows then consume
    the LEFTOVER budget in slot order, each taking up to
    min(wmax, budget, prompt_remaining) prompt tokens. Decode latency
    therefore never stalls behind prefill, prefill rides every step
    instead of alternating with it, spec cycles run while other rows are
    still prefilling, and decode capacity left idle by empty slots flows
    to prefill instead of padding. Rows that fit no budget this step
    ride with row_len == 0.
    """
    rows = list(self.slots)
    if not any(s is not None for s in rows):
      return None
    b = self.max_slots
    row_len = np.zeros((b,), np.int32)
    row_q_pos = np.ones((b,), np.int32)  # empty slot: 1, never SSM-reset 0
    row_seeds = np.zeros((b,), np.int32)
    row_pos = np.zeros((b,), np.int32)
    row_k = np.zeros((b,), np.int32)
    row_w = np.ones((b,), np.int32)
    row_parents = {}
    ids0 = np.zeros((b, 1), np.int32)
    budget = t
    any_spec = False
    width_clamps = 0
    for i, seq in enumerate(rows):
      if seq is None:
        continue
      row_q_pos[i] = seq.pos
      row_seeds[i] = seq.req.seed
      row_pos[i] = seq.n_out
      if seq.state is not SeqState.DECODE:
        continue
      rk = 0
      rw = 1
      if spec_k > 0:
        rk = spec_k if seq.req.spec_k is None else min(seq.req.spec_k, spec_k)
        rk = min(rk, seq.req.max_new - seq.n_out)
        rk = max(rk, 0)
        if rk > 0 and spec_w > 1:
          rw = spec_w if seq.req.spec_w is None else min(seq.req.spec_w,
                                                         spec_w)
          rw = max(rw, 1)
        if rw > 1:
          cap = min(wmax, ragged.MAX_TREE_COLS)
          room = rw * rk   # pageless stack: only the packed-row cap binds
          if self.needs_kv_pages:
            # transient tree writes (slots q_pos+1 .. q_pos+rw*rk) must
            # stay inside the pages reserved at admission: block-table
            # entries past the footprint alias pool page 0, so an
            # unclamped tree near its max_new budget would scatter draft
            # K/V into another sequence's page. Chains can't overflow —
            # rk <= max_new - len(out) already bounds q_pos + rk.
            cap_tok = len(self.alloc.PagesOf(seq.id)) * self.alloc.page_size
            room = cap_tok - 1 - seq.pos
          want = rw
          while rw > 1 and (1 + rw * rk > cap or rw * rk > room):
            rw -= 1
          if rw < want:
            width_clamps += 1
          if rw > 1:
            rk = min(rk, (cap - 1) // rw)
        if rw == 1:
          rk = min(rk, wmax - 1)   # exact chain clamp (pre-tree behavior)
      row_k[i] = rk
      row_w[i] = rw
      any_spec = any_spec or rk > 0
      if not seq.pending:   # a draft source reads it: depth one, never
        ids0[i, 0] = seq.out[-1]
      row_len[i] = 1 + rw * rk
      budget -= 1 + rw * rk
      if rw > 1:
        # DFS preorder parents: branch bi is a chain whose head hangs off
        # the root (-1) and whose depth-d node follows its predecessor
        parents = np.empty((rw * rk,), np.int32)
        for bi in range(rw):
          for d in range(rk):
            j = bi * rk + d
            parents[j] = -1 if d == 0 else j - 1
        row_parents[i] = parents
    assert budget >= 0, (t, row_len)  # engine sizes t for worst-case decode
    prompt_tokens = 0
    for i, seq in enumerate(rows):
      if seq is None or seq.state is not SeqState.PREFILL:
        continue
      n = min(wmax, budget, seq.prompt_remaining)
      row_len[i] = n
      budget -= n
      prompt_tokens += n
    desc = ragged.BuildRaggedRows(row_len, row_q_pos, t, wmax,
                                  row_parents or None)
    tok_ids = np.zeros((t,), np.int32)
    for i, seq in enumerate(rows):
      n = int(row_len[i])
      if seq is None or n == 0:
        continue
      cols = desc.row_cols[i, :n]
      if seq.state is SeqState.PREFILL:
        tok_ids[cols] = seq.req.prompt[seq.pos:seq.pos + n]
      else:
        # draft columns stay 0 until Draft. A row whose newest draw is still
        # on the device names its column there instead (RaggedBatch)
        tok_ids[cols[0]] = (-1 - seq.src_col if seq.pending
                            else seq.out[-1])
      if self.needs_kv_pages:
        # prefix sharing invariant: every slot this row writes (and, on
        # spec rollback, REWRITES) lives in pages CoW-private to it —
        # never shared with another request or the cache
        self.alloc.AssertExclusive(seq.id, seq.pos, n)
    self.width_clamps += width_clamps
    return RaggedBatch(tok_ids, desc, rows, prompt_tokens > 0,
                       prompt_tokens, row_seeds, row_pos, row_k, any_spec,
                       ids0, row_w=row_w, width_clamps=width_clamps)

  def _Finish(self, seq: Sequence, done_eos: bool):
    """The sequence's last token is in `out`: counts it finished and gives
    back whatever it still holds. A row that ended by length left its slot
    when its last step was dispatched (AdvanceRaggedStep); one that meets
    eos holds slot and pages until here; one that meets eos while parked
    PREEMPTED (its draw was in flight when it was spilled) leaves the
    parked queue and the host tier."""
    if seq.state is SeqState.PREEMPTED:
      self.preempted.remove(seq)
      if self.host_store is not None:
        self.host_store.Drop(seq.id)
    elif seq.slot is not None and self.slots[seq.slot] is seq:
      self.slots[seq.slot] = None
    seq.pending = 0    # a later draw still in flight is void
    self.finished += 1
    self._Retire(seq, SeqState.FINISHED, "eos" if done_eos else "length")

  def AdvanceRaggedStep(self, batch: RaggedBatch):
    """The half of a step's commit that needs no token values, done when the
    step is dispatched: every row's cursor passes the tokens the step
    writes, a prefill that used up its prompt becomes a decoder (and its
    full prompt pages go into the prefix cache), each row that draws a
    token has the draw's column noted (`batch.out_col`, and `seq.src_col`
    for the next step's build) and counted (`seq.pending`, so `seq.n_out`
    is the next output position), and a row whose draw is its max_new-th
    has ended BY LENGTH whatever the token turns out to be: it leaves its
    slot here.
    Speculating rows (row_k > 0) are left alone: their cursor follows the
    verify result, so CommitRaggedStep moves it.

    Giving a finished row's slot, pages and state row to the next
    admission while its last step is still running is safe because the
    device runs programs in dispatch order: whatever the next owner writes
    there, or resets at its row_q_pos == 0, comes in a later program on
    the same stream, behind this step's last read and write."""
    assert not batch.advanced
    batch.advanced = True
    self.steps_in_flight += 1
    desc = batch.rows_desc
    for i, seq in enumerate(batch.rows):
      n = int(desc.row_len[i])
      if seq is None or n == 0:
        continue
      if seq.state is SeqState.PREFILL:
        seq.pos += n
        if seq.prompt_remaining > 0:
          if self.kind_pages is not None:
            self.kind_pages.Advance(seq.id, seq.pos)
          continue                       # more prompt tokens to go
        col = int(desc.row_cols[i, n - 1])
        seq.state = SeqState.DECODE
        if self.prefix_cache is not None and self.needs_kv_pages:
          n_full = len(seq.req.prompt) // self.alloc.page_size
          if n_full > 0:
            self.prefix_cache.Insert(
                seq.req.prompt, self.alloc.PagesOf(seq.id)[:n_full])
      elif seq.state is SeqState.DECODE and batch.row_k[i] == 0:
        seq.pos += 1                     # the fed-back token is cached
        col = int(desc.row_cols[i, 0])
      else:
        continue
      batch.out_col[i] = seq.src_col = col
      seq.pending += 1
      if self.kind_pages is not None:
        self.kind_pages.Advance(seq.id, seq.pos)
      if seq.n_out >= seq.req.max_new:
        self.slots[i] = None
        self._Retire(seq, SeqState.FINISHED, "length")

  def CommitRaggedStep(self, batch: RaggedBatch, sampled_tok: np.ndarray,
                       out_tokens=None, accept_len=None) -> list:
    """Folds one ragged step's device outputs back into the state machine
    (after AdvanceRaggedStep, which it runs itself on a batch that has not
    been advanced).

    sampled_tok [T]: the program's per-token draws — token t's draw is a
    pure function of (engine seed, row seed, row output position), so a
    prefill row reads its LAST prompt token's column and a plain decode
    row its only column (`batch.out_col`). out_tokens [B, k+1] /
    accept_len [B]: the verify lane, consumed only by rows with row_k > 0
    (their column-0 entry is bitwise the plain draw, so routing rk == 0
    rows through sampled_tok is equivalent — and keeps the no-spec engine
    free of verify outputs). A row whose sequence ended between dispatch
    and here (cancelled, or eos in the step before) is dropped; one that
    was PREEMPTED keeps its token, because its cursor and its spilled
    pages already hold the step. Returns [(request_id, token, finished:
    bool)] events in slot order, possibly several per speculating row."""
    if not batch.advanced:
      self.AdvanceRaggedStep(batch)
    self.steps_in_flight -= 1
    events = []
    desc = batch.rows_desc
    for i, seq in enumerate(batch.rows):
      if seq is None or desc.row_len[i] == 0:
        continue
      rk = int(batch.row_k[i])
      col = int(batch.out_col[i])
      if seq.state is SeqState.CANCELLED or (col >= 0 and not seq.pending):
        batch.dropped += 1   # ended mid-step: drop the tokens
        continue
      if rk > 0 and seq.state is SeqState.DECODE:
        # spec-verify lane: accepted path + correction/bonus, cursor
        # rollback over every other tree node (pure accounting —
        # rejected slots are re-written next cycle, and reads never pass
        # q_pos + row_len). The engine's in-program KV repair already
        # moved the accepted path's K/V into the canonical chain slots,
        # so advancing seq.pos by m + 1 lands on bit-correct cache state.
        rw = int(batch.row_w[i])
        m = min(int(accept_len[i]), rk)
        self.alloc.NoteRollback(rw * rk - m)
        committed = 0
        for j in range(m + 1):
          tok = int(out_tokens[i, j])
          seq.pos += 1        # verify wrote this column's K/V already
          seq.out.append(tok)
          committed += 1
          done_eos = (seq.req.eos_id is not None and tok == seq.req.eos_id)
          if done_eos or len(seq.out) >= seq.req.max_new:
            self._Finish(seq, done_eos)
            events.append((seq.id, tok, True))
            break
          events.append((seq.id, tok, False))
        if committed < m + 1:
          # accepted tokens truncated by an early eos roll back too
          self.alloc.NoteRollback(m + 1 - committed)
        continue
      if col < 0:
        continue                         # mid-prompt: no draw to deliver
      tok = int(sampled_tok[col])
      seq.pending -= 1
      seq.out.append(tok)
      done_eos = (seq.req.eos_id is not None and tok == seq.req.eos_id)
      if done_eos or len(seq.out) >= seq.req.max_new:
        self._Finish(seq, done_eos)
        events.append((seq.id, tok, True))
      else:
        events.append((seq.id, tok, False))
    return events

  def _FreePages(self, seq_id):
    if self.kind_pages is not None:
      self.kind_pages.Free(seq_id)   # its bookkeeping, then the pool's
    else:
      self.alloc.Free(seq_id)

  def _Retire(self, seq: Sequence, state: SeqState, reason: str):
    seq.state = state
    seq.finish_reason = reason
    self._FreePages(seq.id)   # idempotent
    if self.state_pool is not None:
      self.state_pool.Release(seq.id)   # idempotent

  # -- introspection ---------------------------------------------------------

  def Stats(self) -> dict:
    live = [s for s in self.slots if s is not None]
    host = self.host_store.Stats() if self.host_store is not None else {}
    parked = list(self.preempted) + list(self.waiting)
    return {
        "slots": self.max_slots,
        "slots_live": len(live),
        "queue_depth": len(self.waiting),
        "admitted": self.admitted,
        "finished": self.finished,
        "cancelled": self.cancelled,
        "rejected_overlong": self.rejected_overlong,
        "needs_kv_pages": self.needs_kv_pages,
        "slots_live_peak": self.slots_live_peak,
        "prefix_ordered_admissions": self.prefix_ordered_admissions,
        "width_clamps": self.width_clamps,
        "scheduler_mode": self.scheduler_mode,
        "preemptions": self.preemptions,
        "restores": self.restores,
        "preempted_queued": len(self.preempted),
        "quota_rejections": self.quota_rejections,
        "spilled_pages": host.get("spilled_pages", 0),
        "restored_pages": host.get("restored_pages", 0),
        "host_bytes": host.get("host_bytes", 0),
        # class-aware load signal for the router: work parked ABOVE the
        # default class (a replica drowning in priority traffic should
        # repel more of it even when its plain queue_depth looks fine)
        "queue_depth_high": sum(s.req.priority > 0 for s in parked),
    }
