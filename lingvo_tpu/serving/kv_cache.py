"""KV block allocator: host-side ownership of the global page pool.

The device side of paged serving is a dumb `[num_pages, page_size, N, H]`
pool (attention.InitPagedStates); everything that makes it a cache — which
pages belong to which sequence, which are free — lives here, in plain
Python on the host, updated between device steps. That split keeps every
compiled program shape-static: admitting or evicting a sequence only
rewrites small int32 block tables, never reshapes device buffers.

Allocation policy: a min-heap free list. Always handing out the
lowest-numbered free page keeps the live set packed toward the low end of
the pool — eviction "defragments" by construction (freed high pages sink
to the back of the heap and are reused last), so a long-running server's
working set stays dense without ever copying K/V between pages.

Prefix sharing (serving/prefix_cache.py) adds REFCOUNTS on top: a page
may be referenced by several sequences (a shared system-prompt prefix)
and/or by the prefix cache itself. `Allocate` grants exclusive pages
(refcount 1); `Share` lets a second owner borrow pages already resident;
`Retain`/`Release` are the cache's ownerless references. `Free` only
DECREMENTS — a page returns to the free heap exactly when its last
reference drops, which preserves both standing contracts: `Allocate`
stays all-or-nothing over the free heap, and reclaimed pages re-enter
the same min-heap (lowest-first defrag by construction). `CopyOnWrite`
is the write-hazard escape hatch: before a sequence writes into a page
it does not exclusively own, the scheduler swaps in a fresh private page
(the engine copies the bytes device-side); `AssertExclusive` makes any
missed hazard — including a speculative-decoding rollback rewrite — a
loud failure instead of silent cross-request corruption.

Layers of two kinds (attention.MultiHeadedAttention.window): a stack that
mixes full-attention layers with sliding-window ones keeps ONE pool of
uniform pages that every layer of the scanned block draws from
(transformer.StackedTransformerLayers: `kv_pool`). A page holds page_size
tokens of ONE layer of the block (at every repeat of it), and each layer of
the block has a block table of its own (`KindPages.tables`). A full layer's
row holds every page it ever wrote. A window layer's row holds only the
logical pages its future queries can still read, a run of at most `cap`
consecutive ones that slides with the row's cursor; a page left behind goes
to the row's own tail while the row still grows and back to the pool once it
does not, where a request of either kind finds it. Nothing fixes a share of
the pool for a kind: what the traffic holds of each is what it needs.

O(1)-state mixers (core/ssm.py) need a second, much simpler resource:
`StateSlotPool`. An SSM layer's decode state is a fixed [B, N, H, S]
array — one constant-size matrix per batch row, no growth with sequence
length, nothing to page. Its unit of ownership is the decode SLOT (batch
row) itself, which the scheduler already assigns; the pool just records
which sequence holds which slot and prices it in bytes so admission
accounting and Stats() can compare KV-page HBM against flat mixer-state
HBM (the ISSUE's more-concurrent-requests-at-fixed-HBM criterion).
"""

from __future__ import annotations

import heapq

import numpy as np


class OutOfPages(Exception):
  """Raised by Allocate when the pool cannot satisfy the request."""


# Logical-slot sentinel for a page spilled to the host tier: the owner
# keeps its position in the logical order (so restore scatters the saved
# bytes back to the SAME logical slot) but holds no device page there.
HOLE = -1


class PageAllocator:
  """Owns [0, num_pages) of the device pool; sequences hold disjoint sets.

  NOT thread-safe on its own — the serving engine serializes all calls
  under its scheduler lock. The trash page the engine appends to the
  device pool is outside [0, num_pages) and never managed here.
  """

  def __init__(self, num_pages: int, page_size: int, page_bytes: int = 0):
    assert num_pages > 0 and page_size > 0, (num_pages, page_size)
    self.num_pages = num_pages
    self.page_size = page_size
    # device bytes one logical page costs across EVERY layer's pool, scale
    # sidecars included (metadata only — the engine prices it from its KV
    # census so quantized pools report honest HBM numbers)
    self.page_bytes = int(page_bytes)
    self._free = list(range(num_pages))  # already a valid min-heap
    self._owned: dict[object, list[int]] = {}
    # page -> reference count (sequence owners + cache retains). Absent
    # means free. Pages return to the heap only when this hits 0.
    self._ref: dict[int, int] = {}
    self.peak_in_use = 0
    # speculative-decoding rollback accounting: token slots that were
    # written by a verify step and then rejected. Rollback is pure cursor
    # arithmetic — the scheduler simply doesn't advance `seq.pos` past the
    # accepted prefix, and the next cycle re-writes the same slots (reads
    # are bounded by q_pos + in_len, so stale K/V past the cursor is never
    # attended). No page ever moves; this counter is the only trace.
    self.rolled_back_tokens = 0

  # -- queries ---------------------------------------------------------------

  @property
  def num_free(self) -> int:
    return len(self._free)

  @property
  def num_in_use(self) -> int:
    return self.num_pages - len(self._free)

  def PagesFor(self, num_tokens: int) -> int:
    """Pages needed to hold num_tokens logical slots."""
    return -(-num_tokens // self.page_size)

  def CanAllocate(self, n: int) -> bool:
    return n <= len(self._free)

  def PagesOf(self, seq_id) -> list[int]:
    """The sequence's pages in logical order (index i = logical page i).
    Spilled logical slots read HOLE until FillHoles re-backs them."""
    return list(self._owned[seq_id])

  def HoleCount(self, seq_id) -> int:
    """Logical slots seq_id holds that were spilled (no device page)."""
    return sum(1 for pg in self._owned.get(seq_id, ()) if pg == HOLE)

  def PrivatePages(self, seq_id, num_tokens: int) -> list[tuple[int, int]]:
    """(logical_idx, page) pairs seq_id exclusively owns among the pages
    covering its first num_tokens logical slots — the pages whose BYTES a
    preemption must save to the host tier. Shared pages (a borrowed or
    inserted prefix) stay device-resident across a spill: the sequence's
    reference pins them, so they restore by simply still being there.
    Trailing private pages past the written cursor hold no data and are
    freed without saving."""
    data = self.PagesFor(num_tokens)
    out = []
    for idx, pg in enumerate(self._owned.get(seq_id, ())):
      if idx >= data:
        break
      if pg != HOLE and self._ref.get(pg, 0) == 1:
        out.append((idx, pg))
    return out

  def RefCount(self, page: int) -> int:
    """References on `page` (0 = free)."""
    return self._ref.get(page, 0)

  @property
  def shared_pages(self) -> int:
    """Pages currently referenced more than once (the sharing win)."""
    return sum(1 for r in self._ref.values() if r >= 2)

  def AssertExclusive(self, seq_id, start_token: int, num_tokens: int):
    """Write-hazard guard: every page covering logical token slots
    [start_token, start_token + num_tokens) must be referenced ONLY by
    seq_id. A device write (including a speculative verify step whose
    rejected tail will be re-written after rollback) to a page another
    sequence or the prefix cache references would corrupt their streams;
    copy-on-write at admission is supposed to make this impossible."""
    if num_tokens <= 0:
      return
    pages = self._owned[seq_id]
    lo = start_token // self.page_size
    hi = (start_token + num_tokens - 1) // self.page_size
    for idx in range(lo, min(hi, len(pages) - 1) + 1):
      pg = pages[idx]
      assert pg != HOLE, (
          f"seq {seq_id!r} writing tokens [{start_token}, "
          f"{start_token + num_tokens}) through spilled logical page {idx} "
          "— FillHoles must re-back a restored sequence before any step")
      assert self._ref.get(pg, 0) == 1, (
          f"seq {seq_id!r} writing tokens [{start_token}, "
          f"{start_token + num_tokens}) would touch page {pg} (logical "
          f"{idx}) with refcount {self._ref.get(pg, 0)} — shared pages "
          f"must be copy-on-write'd before any write")

  def Stats(self) -> dict:
    out = {
        "num_pages": self.num_pages,
        "page_size": self.page_size,
        "in_use": self.num_in_use,
        "free": self.num_free,
        "utilization": self.num_in_use / self.num_pages,
        "peak_in_use": self.peak_in_use,
        "num_sequences": len(self._owned),
        "rolled_back_tokens": self.rolled_back_tokens,
        "shared_pages": self.shared_pages,
    }
    if self.page_bytes:
      out["page_bytes"] = self.page_bytes
      out["pool_bytes"] = self.page_bytes * self.num_pages
    return out

  # -- mutations -------------------------------------------------------------

  def Allocate(self, seq_id, n: int) -> list[int]:
    """Grants n MORE pages to seq_id (appended to its logical order).

    All-or-nothing: raises OutOfPages without side effects if fewer than n
    pages are free — the scheduler checks CanAllocate first and queues the
    request instead of admitting it."""
    if n > len(self._free):
      raise OutOfPages(f"need {n} pages, {len(self._free)} free")
    got = [heapq.heappop(self._free) for _ in range(n)]
    for pg in got:
      self._ref[pg] = 1
    self._owned.setdefault(seq_id, []).extend(got)
    self.peak_in_use = max(self.peak_in_use, self.num_in_use)
    return got

  def Share(self, seq_id, pages: list[int]):
    """Appends already-resident `pages` to seq_id's logical order, adding
    one reference each. The free heap is untouched — sharing is how a
    request's footprint stops counting against the pool."""
    if not pages:
      return
    for pg in pages:
      assert self._ref.get(pg, 0) >= 1, f"cannot share free page {pg}"
      self._ref[pg] += 1
    self._owned.setdefault(seq_id, []).extend(pages)

  def Retain(self, page: int):
    """Adds an ownerless reference (the prefix cache holding a page alive
    past its writer's retirement)."""
    assert self._ref.get(page, 0) >= 1, f"cannot retain free page {page}"
    self._ref[page] += 1

  def Release(self, page: int):
    """Drops one ownerless reference (cache eviction/invalidation)."""
    self._DecRef(page)

  def CopyOnWrite(self, seq_id, logical_idx: int):
    """Replaces seq_id's shared logical page with a fresh private one.

    Returns (old_page, new_page) for the engine to copy device-side, or
    None when the page is already exclusive. All-or-nothing like Allocate:
    raises OutOfPages without side effects when the pool is empty."""
    pages = self._owned[seq_id]
    old = pages[logical_idx]
    if self._ref.get(old, 0) == 1:
      return None
    (new,) = self.Allocate(seq_id, 1)
    self._owned[seq_id].pop()        # Allocate appended; splice in place
    pages[logical_idx] = new
    self._DecRef(old)
    return (old, new)

  def SpillPrivate(self, seq_id) -> int:
    """Preemption, device half: releases every page seq_id exclusively
    owns, leaving HOLE sentinels at their logical slots; returns the
    count released. Shared pages (refcount >= 2 — a borrowed prefix, or
    pages the prefix cache retained) KEEP their reference: they stay
    device-resident and un-evictable, which is what makes restore of a
    prefix-sharing sequence correct without re-spilling shared bytes.
    The caller must have gathered the private DATA pages' bytes
    (PrivatePages) to the host tier first — this only drops ownership."""
    pages = self._owned.get(seq_id)
    assert pages is not None, f"spill of unknown sequence {seq_id!r}"
    freed = 0
    for idx, pg in enumerate(pages):
      if pg != HOLE and self._ref.get(pg, 0) == 1:
        self._DecRef(pg)
        pages[idx] = HOLE
        freed += 1
    return freed

  def FillHoles(self, seq_id) -> list[tuple[int, int]]:
    """Restore, device half: re-backs every HOLE with a fresh exclusive
    page, all-or-nothing (raises OutOfPages with no side effects when
    the pool cannot cover them — the scheduler keeps the sequence
    parked). Returns (logical_idx, page) pairs so the engine can scatter
    the host-tier bytes back into exactly the logical slots they left."""
    pages = self._owned.get(seq_id)
    assert pages is not None, f"restore of unknown sequence {seq_id!r}"
    holes = [idx for idx, pg in enumerate(pages) if pg == HOLE]
    if len(holes) > len(self._free):
      raise OutOfPages(
          f"restore needs {len(holes)} pages, {len(self._free)} free")
    got = [heapq.heappop(self._free) for _ in range(len(holes))]
    out = []
    for idx, pg in zip(holes, got):
      self._ref[pg] = 1
      pages[idx] = pg
      out.append((idx, pg))
    self.peak_in_use = max(self.peak_in_use, self.num_in_use)
    return out

  def NoteRollback(self, num_tokens: int):
    """Records num_tokens rejected verify-step writes (cursor rollback)."""
    assert num_tokens >= 0, num_tokens
    self.rolled_back_tokens += int(num_tokens)

  def _DecRef(self, page: int):
    r = self._ref.get(page, 0)
    assert r >= 1, f"double free of page {page}"
    if r == 1:
      del self._ref[page]
      heapq.heappush(self._free, page)
    else:
      self._ref[page] = r - 1

  def FreePages(self, seq_id, pages: list[int]):
    """Drops seq_id's reference on exactly `pages` (exclusive pages of its
    own, in any logical slot): the window kind giving back what its row's
    window has left behind while the row lives on."""
    owned = self._owned[seq_id]
    for pg in pages:
      owned.remove(pg)
      self._DecRef(pg)

  def Free(self, seq_id) -> int:
    """Drops seq_id's reference on every page it holds; returns the count
    of pages released (pages shared with other owners survive — they
    return to the pool when the LAST reference drops).

    Idempotent: freeing an unknown/already-freed id is a no-op (eviction
    and cancellation can race to the same sequence at a step boundary).
    HOLE slots (spilled pages) hold no device reference to drop."""
    pages = self._owned.pop(seq_id, [])
    n = 0
    for pg in pages:
      if pg != HOLE:
        self._DecRef(pg)
        n += 1
    return n


class KindPages:
  """The pages of a stack whose block has layers of two kinds, out of one
  `PageAllocator` of uniform pages (module docstring).

  windows: one entry a layer of the block, 0 = full attention, else the
  keys a query sees counting its own. `tables[s]` is layer s's block table
  `[slots, table_pages]`.

  A query at position i of a window layer reads keys j with
  i - window < j <= i. A row whose next token lands at `pos` (its cursor:
  every later query is at `pos` or after, a prefill chunk's first query
  included) can still read logical page p only if (p + 1) * page_size >
  pos - window + 1. The layer's row therefore holds the logical pages
  [first, end): `first` the page of slot pos - window + 1, `end` at most
  `cap` pages on, where `cap` pages cover the window, the widest step a
  row can take (`max_step_tokens`) and the page boundary: enough for any
  one step's reads and writes together. Admission reserves, a layer,
  min(pages of the whole request, cap), so a row once admitted never waits
  for a page.

  Advance() is called when a step has been DISPATCHED and the cursor has
  passed its tokens: pages wholly behind the new cursor's window are
  released. The step in flight may still read a page released here: the
  device runs programs in dispatch order, and whoever gets the page writes
  it in a later one. The row's table entries behind `first` go stale; the
  kernel never visits them (ops/ragged_block_attend.py, `window`).

  Host bookkeeping only, serialized by the engine's scheduler lock.
  """

  def __init__(self, allocator: PageAllocator, windows, max_step_tokens: int,
               max_slots: int, table_pages: int):
    # a stack with no window layer still keeps a table an owning layer; one
    # with no full layer (every layer holds only what lies behind its cursor:
    # power-retention layers, their open chunk) keeps window tables alone
    assert max_step_tokens > 0 and windows, windows
    self.alloc = allocator
    self.windows = tuple(int(w) for w in windows)
    page = allocator.page_size
    self.caps = tuple(
        min(table_pages, (w + max_step_tokens - 2) // page + 2) if w
        else table_pages for w in self.windows)
    self.tables = np.zeros((len(self.windows), max_slots, table_pages),
                           np.int32)
    # seq -> [slot, logical pages the request ever writes,
    #         a layer: [first logical page held, physical pages from it]]
    self._rows: dict[object, list] = {}
    self.in_use = {"full": 0, "window": 0}
    self.peak_in_use = {"full": 0, "window": 0}
    self.pages_allocated = 0    # window layers' logical pages ever backed
    self.pages_released = 0     # of those, left behind by a live row

  def Footprint(self, total_tokens: int) -> int:
    """Pages admission reserves for a request of total_tokens slots."""
    n = self.alloc.PagesFor(total_tokens)
    return sum(min(n, cap) for cap in self.caps)

  def CanAdmit(self, total_tokens: int) -> bool:
    return self.alloc.CanAllocate(self.Footprint(total_tokens))

  def _Count(self, kind: str, n: int):
    self.in_use[kind] += n
    self.peak_in_use[kind] = max(self.peak_in_use[kind], self.in_use[kind])

  def Admit(self, seq_id, slot: int, total_tokens: int) -> None:
    """Reserves the request's footprint and writes its rows of the tables."""
    n = self.alloc.PagesFor(total_tokens)
    pages = list(self.alloc.Allocate(seq_id, self.Footprint(total_tokens)))
    layers = []
    for s, cap in enumerate(self.caps):
      mine, pages = pages[:min(n, cap)], pages[min(n, cap):]
      self.tables[s, slot, :] = 0
      self.tables[s, slot, :len(mine)] = mine
      layers.append([0, mine])
      self._Count("window" if self.windows[s] else "full", len(mine))
      if self.windows[s]:
        self.pages_allocated += len(mine)
    self._rows[seq_id] = [slot, n, layers]

  def Held(self, seq_id, layer: int) -> tuple[int, list[int]]:
    """(first logical page, physical pages of the run from it) of seq_id's
    row in the block's layer `layer`."""
    first, pages = self._rows[seq_id][2][layer]
    return first, list(pages)

  def Advance(self, seq_id, pos: int) -> int:
    """The row's cursor is now `pos`: its window layers let go of the pages
    no query at or after it can read. Returns pages released."""
    row = self._rows.get(seq_id)
    if row is None:
      return 0
    slot, total, layers = row
    released = 0
    for s, (window, cap) in enumerate(zip(self.windows, self.caps)):
      if not window:
        continue
      first, pages = layers[s]
      new_first = max(0, pos - window + 1) // self.alloc.page_size
      new_first = min(new_first, first + len(pages))
      k = new_first - first
      if k <= 0:
        continue
      left, pages = pages[:k], pages[k:]
      end = first + k + len(pages)
      grow = min(total, new_first + cap) - end
      recycled, freed = left[:grow], left[grow:]
      self.tables[s, slot, end:end + len(recycled)] = recycled
      if freed:
        self.alloc.FreePages(seq_id, freed)
        self._Count("window", -len(freed))
      layers[s] = [new_first, pages + recycled]
      self.pages_allocated += len(recycled)
      self.pages_released += k
      released += k
    return released

  def Free(self, seq_id) -> int:
    row = self._rows.pop(seq_id, None)
    if row is not None:
      for s, (_, pages) in enumerate(row[2]):
        self._Count("window" if self.windows[s] else "full", -len(pages))
    return self.alloc.Free(seq_id)

  def Stats(self) -> dict:
    """The pool's own section (`PageAllocator.Stats`: one pool, so its
    peak is the peak) and, by kind, the pages held now and at most."""
    out = self.alloc.Stats()
    out["kinds"] = {kind: {"in_use": self.in_use[kind],
                           "peak_in_use": self.peak_in_use[kind]}
                    for kind in ("full", "window")}
    out["window_pages_released"] = self.pages_released
    out["window_pages_allocated"] = self.pages_allocated
    out["window_cap_pages"] = max(
        (cap for cap, w in zip(self.caps, self.windows) if w), default=0)
    return out


class StateSlotPool:
  """Ownership of O(1) mixer-state slots (one per decode batch row).

  Device-side the state is a `[num_slots, ...]` array per SSM layer
  (ssm.GatedSSMLayer.InitPagedStates); row i belongs to whichever
  sequence the scheduler placed in decode slot i, and is reset device-
  side on that sequence's first step (q_pos == 0), so acquisition never
  touches the device. Like PageAllocator this is host bookkeeping only,
  serialized by the engine's scheduler lock.

  bytes_per_slot: per-sequence mixer-state HBM across ALL SSM layers
  (sum of StateBytesPerSlot) — constant in sequence length, which is the
  whole point; Stats() exposes it next to the allocator's page numbers.
  """

  def __init__(self, num_slots: int, bytes_per_slot: int):
    assert num_slots > 0 and bytes_per_slot >= 0, (num_slots, bytes_per_slot)
    self.num_slots = num_slots
    self.bytes_per_slot = int(bytes_per_slot)
    self._slot_of: dict[object, int] = {}
    self._owner: dict[int, object] = {}
    self.peak_in_use = 0

  @property
  def num_in_use(self) -> int:
    return len(self._slot_of)

  @property
  def num_free(self) -> int:
    return self.num_slots - len(self._slot_of)

  def Acquire(self, seq_id, slot: int):
    """Binds seq_id to decode slot `slot` (must be free)."""
    assert 0 <= slot < self.num_slots, (slot, self.num_slots)
    assert slot not in self._owner, (
        f"slot {slot} already owned by {self._owner[slot]!r}")
    assert seq_id not in self._slot_of, seq_id
    self._slot_of[seq_id] = slot
    self._owner[slot] = seq_id
    self.peak_in_use = max(self.peak_in_use, self.num_in_use)

  def Release(self, seq_id) -> bool:
    """Unbinds seq_id's slot. Idempotent, mirroring PageAllocator.Free."""
    slot = self._slot_of.pop(seq_id, None)
    if slot is None:
      return False
    del self._owner[slot]
    return True

  def SlotOf(self, seq_id):
    return self._slot_of.get(seq_id)

  def Stats(self) -> dict:
    return {
        "num_slots": self.num_slots,
        "bytes_per_slot": self.bytes_per_slot,
        "in_use": self.num_in_use,
        "free": self.num_free,
        "peak_in_use": self.peak_in_use,
        "state_bytes_in_use": self.num_in_use * self.bytes_per_slot,
    }


def ReadsPages(mixer) -> bool:
  """Whether a mixer reads the page pool: every mixer but one that keeps a
  slot state and nothing else."""
  return (hasattr(mixer, "KvBytesPerToken")
          or not hasattr(mixer, "StateBytesPerSlot"))


def StackCensus(task, kv_cache_dtype=None):
  """What `task.stack` keeps of a sequence while it decodes, counted and
  priced from the stack's own `MixerLayers()`: what `PageAllocator(
  page_bytes=)` and `StateSlotPool(bytes_per_slot)` above are given. None for
  a task with no stack (a non-LM task under GShardDecode).

  A mixer keeps a slot state iff it exposes StateBytesPerSlot (core/ssm.py)
  and prices its pages iff it exposes KvBytesPerToken; one that exposes
  neither is a paged-KV attention layer too. A mixer may hold both
  (core/retention.PowerRetention) and is then counted under both.
  kv_cache_dtype: the engine's override of the layers' own (quant/kv.py)."""
  stack = getattr(task, "stack", None)
  if stack is None:
    return None
  census = {"num_attention": 0, "num_ssm": 0,
            "decode_state_bytes_per_slot": 0, "kv_cache_dtype": None,
            "kv_bytes_per_token": 0, "attention_layers": 0}
  for mixer, reps in stack.MixerLayers():
    if hasattr(mixer, "StateBytesPerSlot"):
      census["num_ssm"] += reps
      census["decode_state_bytes_per_slot"] += reps * mixer.StateBytesPerSlot()
    if ReadsPages(mixer):
      census["num_attention"] += reps
    if hasattr(mixer, "KvBytesPerToken"):
      census["attention_layers"] += reps
      census["kv_bytes_per_token"] += int(
          reps * mixer.KvBytesPerToken(kv_cache_dtype))
      if census["kv_cache_dtype"] is None:
        census["kv_cache_dtype"] = mixer.KvCacheDtype(kv_cache_dtype)
  return census


class SpillEntry:
  """One preempted sequence's host-tier state.

  logical_idxs: which logical pages the saved blocks re-occupy at
  restore (only the PRIVATE pages that held written data — shared
  prefix pages never leave the device, and trailing reserved pages
  hold no data worth moving). blocks: per-paged-leaf host arrays, each
  [len(logical_idxs), ...] in logical_idxs order — int8 K/V pools and
  their f32 scale sidecars are separate leaves and ride along
  unchanged; None on device-free schedulers (unit tests). state_row:
  per-slot-leaf host arrays of the sequence's O(1) mixer state row
  (None for attention-only stacks).
  """

  __slots__ = ("logical_idxs", "blocks", "state_row", "nbytes")

  def __init__(self, logical_idxs, blocks, state_row):
    self.logical_idxs = list(logical_idxs)
    self.blocks = blocks
    self.state_row = state_row
    n = 0
    for arr in (blocks or []):
      n += getattr(arr, "nbytes", 0)
    for arr in (state_row or []):
      n += getattr(arr, "nbytes", 0)
    self.nbytes = int(n)


class HostPageStore:
  """The host memory tier preempted KV pages and SSM state spill to.

  Pure host bookkeeping (numpy blocks in a dict), serialized by the
  engine lock like the allocator. The contract that makes preemption
  invisible to the stream: Put saves the exact device bytes (the engine
  gathers pages through the same jitted page IO the fleet handoff
  uses, so the round trip is a bitwise memcpy), Pop returns them once
  for the restore scatter, Drop discards a cancelled sequence's entry.
  Counters feed scheduler Stats(): host_bytes is the live tier size,
  spilled/restored pages are monotonic totals.
  """

  def __init__(self):
    self._entries: dict = {}
    self.spilled_pages = 0
    self.restored_pages = 0
    self.host_bytes = 0
    self.peak_host_bytes = 0

  def __len__(self) -> int:
    return len(self._entries)

  def __contains__(self, seq_id) -> bool:
    return seq_id in self._entries

  def Put(self, seq_id, logical_idxs, blocks=None, state_row=None):
    assert seq_id not in self._entries, f"double spill of {seq_id!r}"
    entry = SpillEntry(logical_idxs, blocks, state_row)
    self._entries[seq_id] = entry
    self.spilled_pages += len(entry.logical_idxs)
    self.host_bytes += entry.nbytes
    self.peak_host_bytes = max(self.peak_host_bytes, self.host_bytes)
    return entry

  def Peek(self, seq_id) -> SpillEntry:
    return self._entries[seq_id]

  def Pop(self, seq_id) -> SpillEntry:
    entry = self._entries.pop(seq_id)
    self.restored_pages += len(entry.logical_idxs)
    self.host_bytes -= entry.nbytes
    return entry

  def Drop(self, seq_id) -> bool:
    """Discards a cancelled sequence's entry (not counted as restored)."""
    entry = self._entries.pop(seq_id, None)
    if entry is None:
      return False
    self.host_bytes -= entry.nbytes
    return True

  def Stats(self) -> dict:
    return {
        "entries": len(self._entries),
        "spilled_pages": self.spilled_pages,
        "restored_pages": self.restored_pages,
        "host_bytes": self.host_bytes,
        "peak_host_bytes": self.peak_host_bytes,
    }
