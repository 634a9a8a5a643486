"""One ragged kernel for decode, chunked prefill, and spec-verify.

`ops/block_decode.py` gave each SEQUENCE one query per step; prefill and
the spec-verify window needed their own multi-query lowerings, so the
serving engine compiled three step programs and padded prefill rows to a
static chunk. This op is the unification the Ragged Paged Attention
formulation actually calls for: the batch axis is a PACKED TOKEN axis.
Each of the T query tokens carries

- `row_of[t]`  — which batch row (block table) it belongs to, and
- `q_end[t]`   — one past the global KV slot it may attend, i.e. its own
  causal horizon `q_pos + 1` within its sequence.

A `q_len=1` decode row contributes one token, a prefill chunk contributes
`q_len` tokens with ascending `q_end` (causal within the chunk for free —
each token simply sees a shorter prefix), and a spec-verify window is
`k+1` tokens the same way. One op, one compiled program; rows of wildly
different query lengths pack densely instead of padding to the widest.

Layout contract (the serving engine maintains it, same as block_decode):
- a row's logical slot s lives at pool page `block_tables[row, s // P]`,
  offset `s % P`; the K/V for every query token were written BEFORE the
  call (scatter-before-read), so token t's newest visible slot is its own.
- table entries past a row's live pages are unspecified — freed pages may
  already belong to another sequence and must never influence the output.
- `q_end[t] = 0` marks a PADDING token: output 0, no pages read.
- a row's valid tokens are CONTIGUOUS on the packed axis (a row is one run
  of equal `row_of`; `core/ragged.BuildRaggedRows` packs rows so). Padding
  tokens may sit anywhere and their `row_of` is not looked at.
- q arrives PRE-SCALED, exactly like BlockDecode/FlashDecode.

Two lowerings of the same arithmetic (bf16 or int8 pages, f32 scores, f32
online softmax and accumulator), held to each other within rounding:

- `_PallasRaggedAttend` — the kernel's unit of work is (a block of up to
  `Bq` consecutive queries of ONE row, one logical page), and its grid is
  the list of such pairs the step HOLDS: one axis of `pairs` programs, a
  traced length (the plan's count of live pairs), blocks in packed order and
  a block's pages ascending. A page comes into VMEM once per query block,
  not once per token; a block the step does not hold, a page past a block's
  widest horizon or behind its window is no program at all, and a step
  without rows runs none.
  - Block descriptors (`_BuildQueryBlocks`: row, first packed token, valid
    queries, first and last live page, per-query mask columns) and the list
    of pairs (`_LivePairs`: a pair's block and page, `NB * grid_pages`
    entries of room, `NB = B + T // Bq` the static bound on blocks) are a
    few integer ops on `row_of`/`q_end`, computed in the jitted step and
    shipped from nowhere; they ride scalar prefetch, so the page index map
    resolves `block_tables[row[blk[k]], page[k]]` before the DMA is issued.
    They depend on the step's rows, on shapes and on the window, not on the
    layer: a stack builds them once a step for every `PlanKey` its layers
    declare (`BuildAttendPlan`, core/attention.BuildRaggedPlan) and hands
    them to each call as `plan`; a call without one builds its own.
  - A row starts at any packed offset, so q and the output stay in HBM and
    each block copies its own `[Bq, N, H]` window in at its first page and
    out at its last (`Bq` rows of slack past T). A query that is not the
    block's computes to an exact zero; programs run in packed order, so
    the next block overwrites the zeros a block leaves past its own rows,
    and what is left over padding is the zeros padding must read.
  - Masks are per query: the causal horizon and the tree ancestor bits are
    `[Bq, 1]` columns against the `[1, P]` slot iota.
  - Only a live pair is ever named, so a stale table entry never reaches
    VMEM: by construction, not by a clamp.
  - Where a KV head serves a group of query heads (`_GroupedAttendKernel`)
    the unit is (block, SPAN of up to G = `_GROUPED_SPAN` consecutive logical
    pages) for a block that is one token's group, a decode row, and (block,
    one page) for every other. The plan's list holds an entry a span
    (`PlanKey.span`; `ceil(pages / G)` entries from the block's `page0`,
    wherever a window puts it), the pools stay in HBM, and a program's pages
    are copied into one half of a `[2, G, P * heads, h]` scratch by the
    program BEFORE it while that one computes on the other half. A slot of a
    span past the block's last page is no copy and no work: the guarantee
    above holds a slot at a time, a copy names `tables[row, page + s]` for
    `page + s <= last` alone. A span's pages run in one basic block with the
    block's statistics in registers, so a page's products need not wait for
    the softmax of the page before (that chain, not a program's fixed cost,
    was a decode pair's time: `_GroupedAttendKernel` has the readings).
    Pages in order, the same float operations a page: the output is bitwise
    the grid of a page a program.
  - `Bq` is `QueryBlock(shapes, dtypes)`: one page of queries, halved
    while the working set passes the scoped-VMEM budget. The arithmetic
    adapts per block to what the kernel sees: a one-query block (a decode
    row) reads the page as the `[P*N, H]` matrix it already is and runs
    all heads through two plain matmuls masked to the stripe where the
    key's head is the query's (nothing is re-laid out, and a decode row is
    bound by the page's bytes); a larger block runs head-batched
    `[Bq, N, H] x [P, N, H]`, the page re-laid out once for Bq queries.
    Where a KV head serves a group of query heads the group rides the
    packed axis and `_GroupedAttendKernel` runs plain products a KV head;
    its blocks adapt the same way, by rows: a block's products, scratch
    traffic and copies run over the first rung of `BlockRungs` that holds
    its valid queries (a decode row's 8, or Bq).
- `_XlaRaggedAttend` — the CPU serving path and the twin the kernel is
  held to: `fori_loop` with a dynamic trip count of `ceil(max(q_end) / P)`
  over per-token gathered pages through `flash_decode._PageAttend`. Tokens
  whose horizon falls short of the batch max process extra pages fully
  masked, a bitwise no-op (alpha == 1, p == 0). A T-token all-decode pack
  reproduces `BlockDecode` bit for bit, which is what lets the engine
  collapse to one program without moving a single token (asserted in
  tests).

Both dequantize int8 pages through the same `_DequantPages`. The kernel's
products have a free dimension of Bq (or sum over the stripe) where the
twin's have one query, so sums run in another order: the twins agree to
rounding (2.4e-7 in f32 under the interpreter), not to the bit.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lingvo_tpu import observe
from lingvo_tpu.ops.flash_attention import (  # single source of truth
    LANES, NEG_INF, SUBLANES)
from lingvo_tpu.ops.flash_decode import _DotF32, _Finish, _PageAttend
from lingvo_tpu.ops.block_decode import _DequantPages
from lingvo_tpu.ops.block_decode import SupportedOnTpu  # noqa: F401  (same
# Mosaic tiling gate: page_size and h on the 128-lane minor axes; re-exported
# so callers gate the ragged kernel through one name per op module.)


# -- XLA twin (the CPU serving path) -----------------------------------------


def _AncestorOk(slot, c, lo, hi):
  """In-step ancestor visibility for key slots `slot` (already [?, P]).

  c = slot - q_start (position within the row's packed step window); bit c
  of the token's (lo | hi << 32) mask says whether step column c is an
  ancestor-or-self. Slots below the window (c < 0, the committed prefix)
  clip to bit 0, which every tree mask sets (the root is an ancestor of
  all); chain rows ship lo = hi = -1 so every bit reads 1 and the combined
  mask stays bitwise the pre-tree causal mask. Slots at c >= 64 only occur
  on chain rows (tree rows are capped at 64 columns), where -1 again
  yields 1."""
  cc = jnp.clip(c, 0, 63)
  word = jnp.where(cc < 32, lo, hi)
  sh = jnp.where(cc < 32, cc, cc - 32)
  return jnp.bitwise_and(jax.lax.shift_right_logical(word, sh), 1) == 1


def _XlaRaggedAttend(q, k_pool, v_pool, block_tables, row_of, q_end,
                     page_size: int, k_scale=None, v_scale=None,
                     q_start=None, anc_lo=None, anc_hi=None, window: int = 0):
  """q: [T, N, H]; pools [NP, P, N, H]; tables [B, t_pages] int32;
  row_of/q_end [T] int32. -> [T, N, H].

  Dynamic trip count over the batch-max live page: per step the work is
  O(T * max(q_end)), not O(T * t_pages * P). k_scale/v_scale [NP, N, P]
  switch on the int8 path via the shared `_DequantPages`. q_start/anc_lo/
  anc_hi [T] int32 add per-token in-step ancestor masking for tree rows
  (None = chain semantics, bitwise the unmasked kernel). window > 0: token t
  sees slots [q_end - window, q_end) only, the loop starts at the first page
  any token can reach, and a token's page index is held inside its own
  reach, so a table entry behind its window is never gathered."""
  t, n, h = q.shape
  np_total, page, _, _ = k_pool.shape
  assert page == page_size, (page, page_size)
  t_pages = block_tables.shape[1]
  ends = q_end.astype(jnp.int32)
  if q_start is None:
    q_start = jnp.zeros((t,), jnp.int32)
    anc_lo = anc_hi = jnp.full((t,), -1, jnp.int32)
  starts = q_start.astype(jnp.int32)
  lo = anc_lo.astype(jnp.int32)
  hi = anc_hi.astype(jnp.int32)
  trip = jnp.clip((jnp.max(ends) + page_size - 1) // page_size, 0, t_pages)
  tables = jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1)
  rows = jnp.clip(row_of.astype(jnp.int32), 0, tables.shape[0] - 1)
  tok_tables = tables[rows]                                # [T, t_pages]
  first_trip = 0
  if window:
    tok_lo = jnp.maximum(ends - window, 0) // page_size    # [T]
    tok_last = jnp.maximum(ends - 1, 0) // page_size
    first_trip = jnp.min(jnp.where(ends > 0, tok_lo, t_pages))
    first_trip = jnp.minimum(first_trip, trip)

  batched_attend = jax.vmap(_PageAttend)

  def _Body(j, carry):
    m, l, acc = carry
    if window:
      pid = jnp.take_along_axis(
          tok_tables, jnp.clip(j, tok_lo, tok_last)[:, None], axis=1)[:, 0]
      # a padding token has no reach: it reads the pool's last page (the
      # engine's trash page), never a row's
      pid = jnp.where(ends > 0, pid, np_total - 1)
    else:
      pid = jax.lax.dynamic_index_in_dim(tok_tables, j, axis=1,
                                         keepdims=False)
    k_page = k_pool[pid]                                   # [T, P, N, H]
    v_page = v_pool[pid]
    if k_scale is not None:
      k_page = _DequantPages(k_page, k_scale[pid])
      v_page = _DequantPages(v_page, v_scale[pid])
    slot = j * page_size + jnp.arange(page_size, dtype=jnp.int32)  # [P]
    causal = slot[None, :] < ends[:, None]                 # [T, P]
    if window:
      causal &= slot[None, :] >= ends[:, None] - window
    ok = _AncestorOk(slot[None, :], slot[None, :] - starts[:, None],
                     lo[:, None], hi[:, None])
    keep = (causal & ok).astype(jnp.float32)[:, None, :]
    return batched_attend(q, k_page, v_page, keep, m, l, acc)

  m0 = jnp.full((t, n, 1), NEG_INF, jnp.float32)
  l0 = jnp.zeros((t, n, 1), jnp.float32)
  acc0 = jnp.zeros((t, n, h), jnp.float32)
  _, l, acc = jax.lax.fori_loop(first_trip, trip, _Body, (m0, l0, acc0))
  return _Finish(l, acc, q.dtype)


# -- Pallas TPU kernel -------------------------------------------------------

_VMEM_BUDGET = 12 * 2**20   # of the 16 MiB a kernel may scope by default


_GROUPED_BQ = 512   # queries a block of the grouped kernel: 64 tokens by a
#                     group padded to 8 (PERF.md section 6, PR 35)
_GROUPED_SPAN = 4   # logical pages a program of the grouped kernel walks where
#                     its block is one token's group (a decode row), in one
#                     basic block. us a decode pair at 2 / 4 / 8 (`tools/
#                     kernel_probe.py --case grouped_attend`, PERF.md section
#                     6, PR 64): 0.72 0.70 / 0.69 0.69 / 0.70 0.72 at 28 heads
#                     (full, window), 0.68 0.70 / 0.64 0.71 / 0.63 0.76 at 32,
#                     0.83 / 0.74 / 0.75 at heads of 64; the parent 0.82-0.87
#                     and 1.33


def GroupLanes(group: int) -> int:
  """Queries a token lays on the packed axis where the grouped kernel runs:
  its group of query heads padded to whole sublane tiles."""
  return -(-group // SUBLANES) * SUBLANES


def Grouped(n: int, n_kv: int) -> bool:
  """Whether the Pallas lowering runs the grouped kernel: a KV head serves a
  group of more than one query head. Plain multi-head attention keeps the
  head-batched kernel."""
  return n != n_kv


def TileHeads(n: int, n_kv: int, h: int) -> int:
  """KV heads a token's row of the pool holds side by side on its lanes,
  `[pages, P, n_kv / r, r * h]`: 2 where the grouped kernel serves heads of
  half a lane tile (64), which it attends a pair a tile (the pairs one, or
  an even number: `_HeadPages`); else 1, the pool as `[pages, P, n_kv, h]`. A
  function of shapes alone, the same on every backend: a pool's layout is its
  owner's word (PooledAttention.PagePool), and `RaggedAttend` and the runs'
  write read it off the pool's shape."""
  return 2 if Grouped(n, n_kv) and 2 * h == LANES and (
      n_kv == 2 or n_kv % 4 == 0) else 1


def Lowering(lowering: str) -> str:
  """'auto' resolved: the Pallas kernel on a TPU, the XLA twin elsewhere."""
  assert lowering in ("auto", "pallas", "xla"), lowering
  if lowering == "auto":
    return "pallas" if jax.default_backend() == "tpu" else "xla"
  return lowering


def QueryBlock(n: int, h: int, page_size: int, q_dtype, kv_dtype,
               grouped: bool = False) -> int:
  """Bq, the most queries of one row that meet a page together.

  One page of queries: the `[Bq, P]` score tile of a head is square, so a
  K page meets as many query rows as it has key rows. Halved while the
  kernel's working set (K and V pages double-buffered, f32 where int8
  pages dequantize; the q block and the f32 accumulator; the lane-broadcast
  softmax statistics; the per-query mask columns; two `[N, Bq, P]` f32
  score tiles) passes `_VMEM_BUDGET`. A function of shapes and dtypes
  alone: the counters read it (a mixer's `StepCounts`), nothing chooses it."""
  q_bytes = jnp.dtype(q_dtype).itemsize
  kv_bytes = jnp.dtype(kv_dtype).itemsize
  lanes_p = max(page_size, LANES)

  def _WorkingSet(bq):
    pages = 2 * 2 * page_size * n * h * kv_bytes
    if kv_bytes == 1:
      pages += 2 * page_size * n * h * 4
    q_acc = bq * n * h * (q_bytes + 4)
    stats = 2 * n * bq * LANES * 4
    cols = 2 * bq * LANES * 4
    scores = 2 * n * bq * lanes_p * 4
    return pages + q_acc + stats + cols + scores

  if grouped:
    # plain [Bq, H] x [H, P] products a head: two score tiles of one head,
    # and twice the rows amortise a page's fixed cost over twice the queries
    return _GROUPED_BQ
  bq = max(min(page_size, LANES), 8)
  while bq > 8 and _WorkingSet(bq) > _VMEM_BUDGET:
    bq //= 2
  return bq


def BlockRungs(bq: int, lanes: int = 1) -> tuple[int, ...]:
  """The rows of M a query block may run, ascending: a block of n valid
  queries runs the first rung that holds them (`BlockRows`), the kernel
  choosing inside its one program from the count it is handed.

  lanes: the queries one token lays on the packed axis (`GroupLanes` where
  the grouped kernel runs, 1 where the head-batched one does). The lower
  rung is one token's queries (a decode row: the head-batched kernel's
  one-query path, the grouped kernel's 8 rows of a sublane tile), the upper
  one is Bq. No rung between them: 128 rows halve a call over rows of 2 to
  16 tokens (5.29 against 9.09 ms, 64 such rows), which no cell sends, and
  every rung is traced again for every kernel of a step program, 0.4-0.6 s
  each on the benchmark's host (PERF.md section 6, PR 36). A function of
  shapes alone, like `QueryBlock`: the counters read it, nothing chooses it."""
  return (lanes, bq) if lanes < bq else (bq,)


def BlockRows(queries, rungs: tuple[int, ...]):
  """Rows of M the kernel's products run for a block of `queries` valid
  queries (an int or an integer array; 0 queries is no block: 0 rows)."""
  ladder = np.asarray((0,) + tuple(rungs))
  return ladder[np.searchsorted(ladder, queries)]


def NumQueryBlocks(b: int, t: int, bq: int) -> int:
  """Static bound on live query blocks: a row of `len` tokens takes
  ceil(len / Bq) <= len / Bq + 1 of them, rows are contiguous, so B rows
  over T tokens take at most B + T // Bq (and never more than T)."""
  return max(1, min(t, b + t // bq))


def WindowPages(window: int, bq: int, page_size: int, t_pages: int) -> int:
  """Pages a block of Bq consecutive queries of one row can reach: all of
  the row's table without a window; with one, slots [e - window, e + Bq - 1)
  for the block's narrowest horizon e, which touch at most this many."""
  if not window:
    return t_pages
  return min(t_pages, (window + bq - 2) // page_size + 2)


class PlanKey(NamedTuple):
  """What decides a call's query-block descriptors beside the step's rows
  and its tables' shape. All static: a function of a layer's shapes, dtypes
  and window (`AttendPlanKey`), so the layers of a stack that agree in it
  share one `AttendPlan` a step."""
  page_size: int
  window: int
  bq: int        # queries a block holds (QueryBlock)
  lanes: int     # queries a token lays on the packed axis (its group)
  tree: bool     # the rows' tree operands ride the descriptors; else the
  #                chain sentinels (q_start 0, every ancestor bit set)
  kernel: bool   # the Pallas lowering serves the call: a twin's call takes
  #                no descriptors and none are built for it
  clear: bool = False  # the call's kernel reads `AttendPlan.clear_lo` and
  #                `.clear` (_GroupedAttendKernel's does, and ops/
  #                latent_attend.py's); built for no other key
  span: int = 1  # logical pages an entry of the plan's list stands for where
  #                its block runs the rung with one body (`ClearRung`: a decode
  #                row); every other block's entry, and every entry at 1, is a
  #                page (_GroupedAttendKernel walks spans, no other kernel)


def AttendPlanKey(n: int, n_kv: int, h: int, page_size: int, q_dtype,
                  kv_dtype, *, window: int = 0, tree: bool = True,
                  lowering: str = "auto") -> PlanKey:
  """The PlanKey of RaggedAttend called with `n` query heads of size `h`
  over `n_kv` KV heads at these dtypes."""
  kernel = Lowering(lowering) == "pallas"
  grouped = kernel and Grouped(n, n_kv)
  lanes = GroupLanes(n // n_kv) if grouped else n // n_kv
  # the grouped kernel tells a clear page from another; the head-batched one
  # runs one body
  return PlanKey(page_size, int(window),
                 QueryBlock(n_kv, h, page_size, q_dtype, kv_dtype,
                            grouped=grouped), lanes, tree, kernel,
                 clear=grouped, span=_GROUPED_SPAN if grouped else 1)


class AttendPlan(NamedTuple):
  """Descriptors of the step's query blocks (all int32; NB static) and the
  list of the (block, page) pairs its kernels' grid runs.

  Block i holds up to Bq consecutive queries of ONE row, starting at
  packed token `first[i]`; its pages are `page0[i] .. last[i]`. Entries
  past the live blocks repeat the last live block with `n == 0`: no pair
  names them. Pair k is block `blk[k]` at logical page `page[k]`, blocks in
  packed order and a block's pages ascending; `pairs` of them are live, and
  the entries past those repeat the last live pair. Under a key whose `span`
  is G > 1 an entry of a block of at most `ClearRung` queries (a decode row)
  stands for the block's pages `page[k] .. min(page[k] + G - 1, last)`: its
  spans start at `page0` and step by G, ascending, and `pairs` still counts
  the entries, which is the grid's length."""
  row: jnp.ndarray    # [NB] block-table row
  last: jnp.ndarray   # [NB] last live logical page (of the widest horizon)
  page0: jnp.ndarray  # [NB] first logical page a query's window reaches
  #                     (0 without a window)
  n: jnp.ndarray      # [NB] valid queries; 0 = no such block this step
  first: jnp.ndarray  # [NB] packed index of the block's first query
  cols: jnp.ndarray   # [NB, Bq, 4] per query: q_end (0 = not of this
  #                     block), q_start, anc_lo, anc_hi
  col0: tuple         # cols[:, 0]'s four columns, [NB] each: what a
  #                     one-query block's program reads as scalars
  blk: jnp.ndarray    # [NB * grid_pages] a pair's block
  page: jnp.ndarray   # [NB * grid_pages] a pair's logical page
  pairs: jnp.ndarray  # [] the live pairs: the grid's length
  clear: object = None  # [NB] one past the last logical page every query of
  #                     the block sees WHOLE (no mask changes a score there):
  #                     the pages under its narrowest reach. None unless the
  #                     key asks for it (`PlanKey.clear`)
  clear_lo: object = None  # [NB] the first such page: `page0` without a
  #                     window; with one, the first page that lies whole
  #                     inside the window of the block's widest horizon. The
  #                     clear pages are `clear_lo <= page < clear` (none where
  #                     that is empty). None as `clear` is


def _LivePairs(n, page0, last, size: int, span: int = 1, rung: int = 0):
  """(blk, page, pairs) of AttendPlan from its blocks' `n`, `page0`, `last`.

  Block i's pairs are the `last[i] - page0[i] + 1` entries that follow those
  of the blocks before it. So pair k belongs to the last live block with at
  most k pairs before it, and its page is `k + page0[i] - (pairs before i)`
  for that block i. Both are sums over the blocks that have started by k (of
  ones, and of each block's step over the block before it in what it adds to
  k), not lookups by `blk`: the chip runs a lookup an index at a time, and a
  `[NB, size]` compare with two sums is what `first` already costs. size:
  `NB * grid_pages`, which holds any step's pairs whatever pages its rows
  share. (`jax.lax` over constants of numpy, as `_BuildQueryBlocks`.)

  span > 1 (`PlanKey.span`): a block of at most `rung` queries takes an entry
  a SPAN of its pages, `ceil(pages / span)` of them, entry j at page `page0 +
  j * span`: its page is `(k - pairs before i) * span + page0[i]`, and the
  factor of k is a third sum of the same kind. Every other block keeps an
  entry a page. span 1 is the list as it was, operation for operation."""
  lax, i32 = jax.lax, np.int32
  nb = n.shape[0]
  live = lax.gt(n, i32(0))
  count = lax.add(lax.sub(last, page0), i32(1))
  if span > 1:
    walks = lax.le(n, i32(rung))
    stride = lax.select(walks, np.full((nb,), span, i32), np.ones((nb,), i32))
    count = lax.select(walks, lax.div(lax.add(count, i32(span - 1)),
                                      i32(span)), count)
  count = lax.select(live, count, np.zeros((nb,), i32))
  upto = lax.cumsum(count)
  pairs = lax.index_in_dim(upto, nb - 1, 0, keepdims=False)
  before = lax.sub(upto, count)
  k = lax.min(np.arange(size, dtype=i32),
              lax.broadcast(lax.sub(pairs, i32(1)), (size,)))
  over = lambda x: lax.broadcast_in_dim(x, (nb, size), (0,))     # a block's
  started = lax.bitwise_and(
      over(live), lax.le(over(before), lax.broadcast_in_dim(k, (nb, size),
                                                            (1,))))
  # a block's step over the block before it, and what the steps of the blocks
  # started by k add up to: the last started block's own value
  step_of = lambda x: lax.sub(x, lax.pad(x, i32(0), ((1, -1, 0),)))
  of_last = lambda step: lax.reduce_sum(
      lax.select(started, over(step), np.zeros((nb, size), i32)), (0,))
  if span > 1:
    before = lax.mul(before, stride)
  step = step_of(lax.sub(page0, before))
  blk = lax.sub(lax.reduce_sum(lax.convert_element_type(started, i32), (0,)),
                i32(1))
  if span > 1:
    k = lax.mul(k, of_last(step_of(stride)))
  page = lax.add(k, of_last(step))
  # a step with no live block: nothing runs, and the entries name block 0
  return lax.max(blk, i32(0)), lax.max(page, i32(0)), pairs


def _BuildQueryBlocks(row_of, ends, starts, lo, hi, *, bq: int, nb: int,
                      page_size: int, t_pages: int, window: int = 0,
                      clear: bool = False, span: int = 1,
                      lanes: int = 1) -> AttendPlan:
  """Cuts each row's run of tokens into blocks of Bq queries.

  A few [T]- and [NB, Bq]-sized integer ops on what the step already has
  on the device. They depend on nothing a layer computes, yet under a scan
  over layers XLA lifts out of the loop only a part of them (the lookups;
  the `[NB, Bq, 4]` stack and its copies, 0.12 ms a layer at 73 blocks of
  512, stayed; PERF.md section 6, PR 43): `BuildAttendPlan` is called once
  a step, before the scan.

  Written in `jax.lax` over constants of numpy, as ops/run_write.
  BuildWriteRuns is and for its reason: every serving process traces it, and
  through `jnp` it was 71 traces a plan (PERF.md section 6, PR 51)."""
  lax, i32 = jax.lax, np.int32
  t = row_of.shape[0]
  both = lax.bitwise_and
  last_of = lambda x: lax.index_in_dim(x, x.shape[0] - 1, 0, keepdims=False)
  idx = np.arange(t, dtype=i32)
  valid = lax.gt(ends, i32(0))
  prev_valid = lax.pad(valid, np.bool_(False), ((1, -1, 0),))
  prev_row = lax.concatenate(
      [lax.slice(row_of, (0,), (1,)), lax.slice(row_of, (0,), (t - 1,))], 0)
  run_start = both(valid, lax.bitwise_or(lax.bitwise_not(prev_valid),
                                         lax.ne(row_of, prev_row)))
  run_first = lax.cummax(lax.select(run_start, idx, np.zeros((t,), i32)))
  blk_start = both(valid, lax.eq(lax.rem(lax.sub(idx, run_first), i32(bq)),
                                 i32(0)))
  csum = lax.cumsum(lax.convert_element_type(blk_start, i32))
  blk = lax.sub(csum, i32(1))                               # [T] block id
  n_live = last_of(csum)
  k = np.arange(nb, dtype=i32)
  src = lax.min(k, lax.broadcast(lax.max(lax.sub(n_live, i32(1)), i32(0)),
                                 (nb,)))
  # block k starts at the first token whose running count reaches k + 1
  first = lax.reduce_sum(lax.convert_element_type(
      lax.le(lax.broadcast_in_dim(csum, (nb, t), (1,)),
             lax.broadcast_in_dim(src, (nb, t), (0,))), i32), (1,))
  first = lax.min(first, i32(t - 1))
  each = lambda x: lax.broadcast_in_dim(x, (nb, bq), (0,))       # a block's
  in_range = lax.lt(
      lax.add(each(first), np.broadcast_to(np.arange(bq, dtype=i32),
                                           (nb, bq))), i32(t))
  # What a block's queries carry, x[first[k] + j] (past the end: x[T - 1]).
  # A block's tokens are consecutive, so it is one slice a block of the
  # tokens' values laid side by side, not a lookup a query and value: the
  # chip runs a lookup an index at a time (six of 73 x 512: 1.5 ms; the
  # slices 0.07; PERF.md section 6, PR 43)
  per_token = lax.concatenate(
      [lax.reshape(x, (t, 1)) for x in (
          ends, starts, lo, hi, blk, lax.convert_element_type(valid, i32))],
      1)                                                    # [T, 6]
  per_token = lax.concatenate(
      [per_token, lax.broadcast_in_dim(last_of(per_token), (bq, 6), (1,))], 0)
  of_blocks = lax.gather(
      per_token, lax.reshape(first, (nb, 1)),
      lax.GatherDimensionNumbers(offset_dims=(1, 2), collapsed_slice_dims=(),
                                 start_index_map=(0,)),
      slice_sizes=(bq, 6), mode="clip")                     # [NB, Bq, 6]
  part = lambda j: lax.index_in_dim(of_blocks, j, 2, keepdims=False)
  member = both(both(in_range, lax.ne(part(5), i32(0))),
                lax.eq(part(4), each(src)))
  blk_ends = lax.select(member, part(0), np.zeros((nb, bq), i32))
  cols = lax.concatenate([lax.reshape(blk_ends, (nb, bq, 1)),
                          lax.slice_in_dim(of_blocks, 1, 4, axis=2)], 2)
  last = lax.clamp(
      i32(0),
      lax.sub(lax.div(lax.add(lax.reduce_max(blk_ends, (1,)),
                              i32(page_size - 1)), i32(page_size)), i32(1)),
      i32(t_pages - 1))
  n = lax.select(lax.lt(k, lax.broadcast(n_live, (nb,))),
                 lax.reduce_sum(lax.convert_element_type(member, i32), (1,)),
                 np.zeros((nb,), i32))
  # the least of a per-query value over the block's own queries
  narrowest = lambda x: lax.reduce_min(
      lax.select(member, x, np.full((nb, bq), np.iinfo(np.int32).max, i32)),
      (1,))
  page0 = lax.full_like(last, 0)
  if window:
    # the block's narrowest horizon less the window: no query of the block
    # sees a slot before it
    page0 = lax.min(lax.div(lax.max(lax.sub(narrowest(blk_ends), i32(window)),
                                    i32(0)), i32(page_size)), last)
  cleared = clear_lo = None
  if clear:
    # A query sees every slot under its horizon `q_end` if it is a chain's
    # (every ancestor bit set); a tree's sees them under `q_start + 1` too
    # (slots at or below `q_start` clip to bit 0 of `_AncestorOk`, where that
    # is set) and none for sure otherwise. The block's narrowest such reach,
    # in whole pages.
    one = np.ones((nb, bq), i32)
    reach = lax.select(
        lax.eq(both(part(2), part(3)), np.negative(one)), blk_ends,
        lax.select(lax.eq(both(part(2), one), one),
                   lax.min(blk_ends, lax.add(part(1), i32(1))),
                   np.zeros((nb, bq), i32)))
    cleared = lax.min(lax.div(narrowest(reach), i32(page_size)), i32(t_pages))
    clear_lo = page0
    if window:
      # a window's far edge: a query sees no slot before `q_end - window`, so
      # every query of the block sees the slots from its WIDEST horizon less
      # the window on. The first whole page of them.
      clear_lo = lax.div(
          lax.add(lax.max(lax.sub(lax.reduce_max(blk_ends, (1,)), i32(window)),
                          i32(0)), i32(page_size - 1)), i32(page_size))
  col0 = lax.index_in_dim(cols, 0, 1, keepdims=False)       # [NB, 4]
  blk, page, pairs = _LivePairs(
      n, page0, last, nb * WindowPages(window, bq, page_size, t_pages), span,
      ClearRung(BlockRungs(bq, lanes)))
  row = lax.gather(
      row_of, lax.reshape(first, (nb, 1)),
      lax.GatherDimensionNumbers(offset_dims=(), collapsed_slice_dims=(0,),
                                 start_index_map=(0,)),
      slice_sizes=(1,), mode="clip")
  return AttendPlan(row=row, last=last, page0=page0, n=n,
                    first=first, cols=cols,
                    col0=tuple(lax.index_in_dim(col0, c, 1, keepdims=False)
                               for c in range(4)),
                    blk=blk, page=page, pairs=pairs, clear=cleared,
                    clear_lo=clear_lo)


def GridPairs(key: PlanKey, b: int, t: int, t_pages: int) -> int:
  """The pairs a plan's list has room for, `NB * grid_pages`: every block
  any pack of `t` tokens over `b` rows could hold, times every page a
  block's queries could reach. The grid a call ran before it ran the live
  pairs alone."""
  return NumQueryBlocks(b, t * key.lanes, key.bq) * WindowPages(
      key.window, key.bq, key.page_size, t_pages)


def _HostBlocks(key: PlanKey, row_q_pos, row_len, t_pages: int):
  """(live, queries, narrowest horizon, widest horizon, page0, last), `[B,
  blocks]` each, of the blocks a step's rows are cut into, from the host's
  own view of them (numpy): row r brings `row_len[r]` tokens at positions
  `row_q_pos[r] ...`, each `key.lanes` queries of its own horizon, cut into
  blocks of `key.bq`."""
  start = np.asarray(row_q_pos, np.int64)[:, None]
  queries = np.asarray(row_len, np.int64)[:, None] * key.lanes
  lo = np.arange(-(-int(queries.max(initial=0)) // key.bq))[None] * key.bq
  live = lo < queries                                       # [B, blocks]
  hi = np.minimum(lo + key.bq, queries) - 1                 # its last query
  # a query's horizon is its token's slot + 1
  widest = start + hi // key.lanes + 1
  narrowest = start + lo // key.lanes + 1
  last = np.clip(-(-widest // key.page_size) - 1, 0, t_pages - 1)
  page0 = np.zeros_like(last)
  if key.window:
    page0 = np.minimum(
        np.maximum(narrowest - key.window, 0) // key.page_size, last)
  return live, hi - lo + 1, narrowest, widest, page0, last


def ClearRung(rungs: tuple[int, ...]) -> int:
  """The queries a block must pass to run the rung whose programs tell a
  clear page from another (the widest; a lower rung runs one body, over a
  span of its block's pages where the key's `span` says so)."""
  return rungs[-2] if len(rungs) > 1 else 0


def PairCounts(key: PlanKey, row_q_pos, row_len,
               t_pages: int) -> tuple[int, int, int]:
  """(live pairs, clear pairs, programs) of a step from ONE view of its rows
  (numpy; the engine's three counters a key a step):

  - live pairs: the PAGES its blocks attend, whatever the grid's unit is
    (`AttendPlan.pairs` where `key.span` is 1);
  - clear pairs: those of them whose program ran no mask (what the plan's
    `clear_lo` and `clear` give the kernel, counted over CHAIN rows, which is
    what the host knows it sent): a block of the widest rung at a page that
    lies whole under its narrowest horizon and, with a window, whole inside
    the window of its widest. 0 for a key whose kernel reads no `clear`;
  - programs: `AttendPlan.pairs`, the grid's length (`_LivePairs`' twin, as
    `BlockRows` is `BlockRungs`'): a program a page, or a span of `key.span`
    pages where the block runs the rung with one body. Live pairs over it is
    the pages a program: 1.0 where `key.span` is 1."""
  live, queries, narrowest, widest, page0, last = _HostBlocks(
      key, row_q_pos, row_len, t_pages)
  count = lambda x: int(np.sum(np.where(live, x, 0)))
  pages = last - page0 + 1
  wide = queries > ClearRung(BlockRungs(key.bq, key.lanes))
  clear = 0
  if key.clear:
    clear_lo = page0
    if key.window:
      clear_lo = -(-np.maximum(widest - key.window, 0) // key.page_size)
    clear = count(np.where(wide, np.maximum(np.minimum(
        narrowest // key.page_size, last + 1) - clear_lo, 0), 0))
  return count(pages), clear, count(
      np.where(wide, pages, -(-pages // key.span)))


def LivePairs(key: PlanKey, row_q_pos, row_len, t_pages: int) -> int:
  return PairCounts(key, row_q_pos, row_len, t_pages)[0]


def ClearPairs(key: PlanKey, row_q_pos, row_len, t_pages: int) -> int:
  return PairCounts(key, row_q_pos, row_len, t_pages)[1]


def Programs(key: PlanKey, row_q_pos, row_len, t_pages: int) -> int:
  return PairCounts(key, row_q_pos, row_len, t_pages)[2]


def BuildAttendPlan(key: PlanKey, row_of, q_end, q_start=None, anc_lo=None,
                    anc_hi=None, *, b: int, t_pages: int) -> AttendPlan:
  """The descriptors of every call of `key` in a step over these tokens.

  row_of / q_end [T] and the tree operands as RaggedAttend takes them (a
  token each, before its group is laid beside it; `key.tree` says whether
  the tree operands ride); block tables [b, t_pages]. The one way the
  descriptors are built: by a stack once a step, or by a call that was
  handed none."""
  assert key.kernel, key
  assert (q_start is not None) == key.tree, (key, q_start is None)
  lax, i32 = jax.lax, np.int32
  to_i32 = lambda x: lax.convert_element_type(x, i32)
  rows = lax.clamp(i32(0), to_i32(row_of), i32(b - 1))
  ends = to_i32(q_end)
  if q_start is None:
    starts = np.zeros(ends.shape, i32)
    lo = hi = np.full(ends.shape, -1, i32)
  else:
    starts, lo, hi = (to_i32(x) for x in (q_start, anc_lo, anc_hi))
  if key.lanes > 1:
    # a token's group rides the packed axis as consecutive queries of its
    # row with its horizon (RaggedAttend)
    t = ends.shape[0]
    rows, ends, starts, lo, hi = (
        lax.reshape(lax.broadcast_in_dim(x, (t, key.lanes), (0,)),
                    (t * key.lanes,)) for x in (rows, ends, starts, lo, hi))
  return _BuildQueryBlocks(
      rows, ends, starts, lo, hi, bq=key.bq,
      nb=NumQueryBlocks(b, rows.shape[0], key.bq), page_size=key.page_size,
      t_pages=t_pages, window=key.window, clear=key.clear, span=key.span,
      lanes=key.lanes)


def _BlockPageAttend(q, k, v, keep, m, l, acc, dims_qk, dims_pv):
  """`_PageAttend` with a free query dimension: the same float ops in the
  same order per (query, head, slot). keep is boolean and broadcasts
  against the scores; m/l keep a trailing unit dim, or ride lane-replicated
  as wide as the scores (every column a copy: the statistics come back as
  wide, and the accumulator, a whole number of times as wide, is scaled by
  the copies laid side by side). keep None (static): the caller knows every
  query sees every slot of the page, and the passes that only a masked slot
  needs are not traced: the select over the scores, and the guard of a row
  that has seen no slot yet (its maximum is a score's here, never a masked
  slot's -1e30). The result is bitwise that of an all-true `keep`."""
  s = _DotF32(q, k, dims_qk)
  if keep is not None:
    s = jnp.where(keep, s, NEG_INF)
  m_cur = jnp.max(s, axis=-1, keepdims=True)
  m_new = jnp.maximum(m, m_cur)
  m_safe = m_new
  if keep is not None:
    m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
  p = jnp.exp(s - m_safe)
  alpha = jnp.exp(m - m_new)
  l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
  pv = _DotF32(p.astype(v.dtype), v, dims_pv)
  if alpha.shape[-1] > 1:
    alpha = jnp.concatenate(
        [alpha] * (acc.shape[-1] // alpha.shape[-1]), axis=-1)
  return m_new, l_new, acc * alpha + pv


def _RaggedAttendKernel(blk_ref, page_ref, row_ref, last_ref, page0_ref,
                        tables_ref, n_ref, first_ref, end0_ref, start0_ref,
                        lo0_ref, hi0_ref, *rest, page_size: int, window: int):
  """One live (query block, logical page) pair of the plan's list; scratch
  carried over a block's pages.

  Program k runs block `blk_ref[k]` at logical page `page_ref[k]`: the
  block's first page (`page0`) brings its queries in, its last one takes the
  output out, and every program accumulates. With a window (static) a query
  sees slots [q_end - window, q_end) only.

  q_hbm/out_hbm: [T + Bq, N, H], left in HBM: a block copies its own
  window in at its first page and out at its last, at its row's packed
  offset, whatever that is. k_ref/v_ref: [1, P, N, H]; cols_ref:
  [1, Bq, 4]. The block's size decides its arithmetic, inside the one
  program:

  - one query (a decode row): the page is read as the `[P*N, H]` matrix it
    already is in memory and all heads go through two plain matmuls,
    `[N, H] x [P*N, H]^T` and `[N, P*N] x [P*N, H]`, masked to the stripe
    where the key's head is the query's. Nothing is re-laid out; the N-fold
    surplus of MXU work is free beside the page's DMA.
  - more (a prefill chunk, a verify window): head-batched
    `[Bq, N, H] x [P, N, H]`, the page re-laid out once for Bq queries.

  Float and int8 pools share the body (int8 threads two scale blocks,
  dequantized via the shared `_DequantPages`)."""
  pair = pl.program_id(0)
  i, page = blk_ref[pair], page_ref[pair]
  q_hbm, cols_ref, k_ref, v_ref, *rest = rest
  if len(rest) == 13:
    ks_ref, vs_ref = rest[:2]
    rest = rest[2:]
  else:
    ks_ref = vs_ref = None
  (_, out_hbm, q1, qb, m1, l1, acc1, mb, lb, accb, sem) = rest
  nv = n_ref[i]
  first = first_ref[i]
  bq, heads, h = qb.shape
  slot0 = page * page_size

  def _Copy(src, dst):
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()

  def _Block(q_scr, m_scr, l_scr, acc_scr, attend, layout):
    """The block's life over its pages: window in, accumulate, window out.

    attend(k_page, v_page, m, l, acc) -> (m, l, acc); layout puts the
    finished block in q_scr's layout (which doubles as the way out)."""
    window = pl.ds(first, q_scr.shape[0])

    @pl.when(page == page0_ref[i])
    def _Init():
      _Copy(q_hbm.at[window], q_scr)
      m_scr[...] = jnp.full_like(m_scr, NEG_INF)
      l_scr[...] = jnp.zeros_like(l_scr)
      acc_scr[...] = jnp.zeros_like(acc_scr)

    def _Accumulate():
      k_page, v_page = k_ref[0], v_ref[0]
      if ks_ref is not None:
        k_page = _DequantPages(k_page, ks_ref[0])
        v_page = _DequantPages(v_page, vs_ref[0])
      m, l, acc = attend(k_page, v_page, m_scr[..., :1], l_scr[..., :1],
                         acc_scr[...])
      m_scr[...] = jnp.broadcast_to(m, m_scr.shape)
      l_scr[...] = jnp.broadcast_to(l, l_scr.shape)
      acc_scr[...] = acc

    _Accumulate()

    @pl.when(page == last_ref[i])
    def _Emit():
      # a query that is not this block's (q_end 0 in cols) comes out an
      # exact zero: the rows after the block's own are the next block's to
      # overwrite (programs run in packed order) or padding
      q_scr[...] = layout(
          _Finish(l_scr[..., :1], acc_scr[...], q_scr.dtype))
      _Copy(q_scr, out_hbm.at[window])

  def _OneQuery(k_page, v_page, m, l, acc):
    width = page_size * heads
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, width), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (heads, width), 0)
    slot = slot0 + col // heads
    keep = ((col % heads == head) & (slot < end0_ref[i]) & _AncestorOk(
        slot, slot - start0_ref[i], lo0_ref[i], hi0_ref[i]))
    if window:
      keep &= slot >= end0_ref[i] - window
    return _BlockPageAttend(
        q1[0], k_page.reshape(width, h), v_page.reshape(width, h), keep,
        m, l, acc, (((1,), (1,)), ((), ())), (((1,), (0,)), ((), ())))

  def _ManyQueries(k_page, v_page, m, l, acc):
    slot = slot0 + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)                       # [1, P]
    cols = cols_ref[0]                                      # [Bq, 4]
    keep = (slot < cols[:, 0:1]) & _AncestorOk(
        slot, slot - cols[:, 1:2], cols[:, 2:3], cols[:, 3:4])  # [Bq, P]
    if window:
      keep &= slot >= cols[:, 0:1] - window
    return _BlockPageAttend(
        qb[...], k_page, v_page, keep[None], m, l, acc,
        (((2,), (2,)), ((1,), (1,))), (((2,), (0,)), ((0,), (1,))))

  pl.when(nv == 1)(lambda: _Block(
      q1, m1, l1, acc1, _OneQuery, lambda out: out[None]))
  pl.when(nv > 1)(lambda: _Block(
      qb, mb, lb, accb, _ManyQueries, lambda out: jnp.swapaxes(out, 0, 1)))


# which half of a 32-bit word holds the EVEN row of a packed 16-bit pair
_EVEN_ROW_LOW = True


def _HeadPages(ref, heads: int, at=(0,)):
  """ref: pages as rows, `[..., P * heads, H]` with row p * heads + g the
  vector of token p, KV head g; `at` picks ONE page by its leading indices (a
  block `[1, P * heads, H]`: its only one). -> `heads` arrays `[P, H]`, a
  head's rows. 32-bit pages: a strided load a head. 16-bit pages: Mosaic loads
  with a stride only 32-bit rows, and two consecutive rows share a word
  row, so the page is read as words (rows 2w and 2w + 1 in the halves of
  word row w), every (heads / 2)-th word row from s holds heads 2s and
  2s + 1 of every token, and each half, shifted to the top of a word, IS
  the 16-bit float's value as an f32."""
  rows = ref.shape[-2]
  tokens = rows // heads
  at = tuple(at)
  if heads == 1:
    return [ref[at]]
  if jnp.dtype(ref.dtype).itemsize == 4:
    return [ref[at + (pl.ds(g, tokens, stride=heads), slice(None))]
            for g in range(heads)]
  assert ref.dtype == jnp.bfloat16 and heads % 2 == 0, (ref.dtype, heads)
  words = ref.bitcast(jnp.uint32)                      # [..., rows / 2, H]
  out = []
  for s in range(heads // 2):
    w = words[at + (pl.ds(s, tokens, stride=heads // 2), slice(None))]
    low = pltpu.bitcast(w << 16, jnp.float32).astype(ref.dtype)
    high = pltpu.bitcast(w & jnp.uint32(0xFFFF0000),
                         jnp.float32).astype(ref.dtype)
    out += [low, high] if _EVEN_ROW_LOW else [high, low]
  return out


def _GroupedAttendKernel(blk_ref, page_ref, row_ref, last_ref, page0_ref,
                         tables_ref, n_ref, first_ref, end0_ref, start0_ref,
                         lo0_ref, hi0_ref, clear_lo_ref, clear_ref, pairs_ref,
                         *rest, page_size: int, window: int, heads: int,
                         rungs: tuple[int, ...], tile_heads: int = 1,
                         span: int = 1):
  """The (query block, logical page) program where a KV head serves a GROUP
  of query heads: the group rides the packed axis (RaggedAttend), so a
  block is up to Bq queries of which each has one vector per KV head. q and
  the output arrive with heads and head size MERGED on the minor axis,
  `[T + Bq, Nkv * H]`: a head's queries are a 128-lane column slice, whole
  tiles. A page arrives as the pool holds it, `[P, Nkv, H]` seen as
  `[P * Nkv, H]` rows (token-major, head-minor): on the chip that view is
  the SAME bytes (a `(Nkv, 128)`-tiled bf16 array packs row pairs exactly as
  `[P * Nkv, 128]` does), where a view with the heads on the lanes made XLA
  copy every pool every layer (25 of 98 ms a step, PERF.md section 6,
  PR 35). `_HeadPages` takes a head's `[P, H]` keys out of the rows by a
  strided load. The block then runs `heads` plain `[rows, H] x [H, P]` and
  `[rows, P] x [P, H]` products a page, over the rows it HOLDS: its valid
  queries lead its window, and their count (`n_ref`, which the program
  already receives) picks the first of `rungs` (`BlockRungs`, static) that
  holds them, inside the one program. The products, the softmax, the
  scratch traffic and the copies of q and the output in and out of HBM all
  run over that many leading rows of the scratch, so a decode row (one
  token's laid group) pays for 8 rows and not for Bq, and rows past the rung
  are never written: the output starts as zeros and padding reads them. The
  packed axis is the tiled one here, and a block starts at any token, a
  multiple of 8 queries: q and the output cross HBM in f32, whose tile is 8
  rows (a 16-bit tile is 16: half the rows would start mid-tile), and the
  block's queries are cast once, at its first page. Window and masks as in
  _RaggedAttendKernel.

  A program does the vector work its page needs: the widest rung has two
  bodies. A page in `clear_lo_ref[i] <= page < clear_ref[i]` lies whole under
  the horizon of every query of the block and whole inside every query's
  window (`AttendPlan.clear_lo`, `.clear`): its body reads no mask column,
  builds no `keep`, selects nothing and guards no unseen row
  (`_BlockPageAttend(keep=None)`), and carries a head's statistics as the
  lane-replicated `[rows, 128]` the scratch holds, no `[:, :1]` slice in and
  no broadcast out (a page of 128 slots; another page size keeps the `[rows,
  1]` form). Any other page (the one or two the block's own tokens sit in, a
  window's first) takes the masked body as it was. Per (query, head, slot)
  both run the same float ops in the same order: the output is bitwise the
  masked kernel's. A masked page leaves the rung's rows that are not the
  block's at an exact zero by itself, a clear one does not, so `_Emit` zeroes
  them, once a block, by `n_ref`. The 8-row rung keeps its one masked body (a
  second body is a second trace; what its pair costs: below, PR 64). Measured
  (`tools/kernel_probe.py --case grouped_attend`, PERF.md section 6, PR 62): a
  chunk pair 3.75-3.97 us masked -> 1.50-2.01 us with nine in ten of its
  pages clear; the same body over `[rows, 1]` statistics LOST (4.18-4.20 us:
  the mask's passes cost less than the slices and broadcasts it is then left
  with), the rows in halves or quarters gained 4-7% more at two and four
  times the traced body.

  A decode row's program walks a SPAN of its pages (PR 64). A block of the
  rung with one body (one token's group: 8 or 16 rows) is named by the plan
  once a span of up to `span` consecutive logical pages (`PlanKey.span`,
  `_GROUPED_SPAN`; `lead` its first, `pages` how many of them are the
  block's), every other block once a page. The pools stay in HBM: a program
  starts the copies of the NEXT program's pages into one half of the `[2,
  span, P * heads, h]` scratches and awaits its own in the other (`_Fetch`),
  a copy a live page, `tables[row, lead + s]` for `lead + s <= last` alone, so
  a slot past the block's last page is never named, copied or attended: the
  page-reuse guarantee holds a slot at a time. A whole span then runs in ONE
  basic block (`_Pages`): the statistics and the accumulator are read once,
  ride the pages as values and are written once, so the second page's
  products need not wait for the first page's softmax; a row's last, shorter
  span runs a page a trip of a loop. What the probe found (`tools/
  kernel_probe.py --case grouped_attend`, PERF.md section 6, PR 64): a
  program's fixed cost was NOT what a decode pair cost: the same body over
  the same spans one page a loop trip, or a page a branch, read the parent's
  0.83-0.88 us a page at G = 1, 2, 4 and 8 alike (the copies alone 0.40, the
  products alone 0.77-0.84: eight `[8, 128] x [128, 128]` products, each a
  weight load of the MXU, in a chain a page long); the pages of a span in one
  block read 0.64-0.71 us at G = 4 (0.68-0.72 at 2, 0.63-0.76 at 8).

  Heads of HALF a lane tile (tile_heads 2, head size 64): the pool holds two
  KV heads side by side on a token's row (`TileHeads`), `heads` counts those
  rows, and a row of `h` lanes here is the pair's. Nothing is sliced inside a
  tile: each head of the pair runs the SAME two products over the whole row,
  its queries with the tile-mate's lanes zeroed (made once a block, at its
  first page: `qh[j]`), so `q . k` sums its own 64 dims and exact zeros, and
  `p . v` comes back a row wide of which its own lanes are kept; the pair's
  accumulator stays one row, each half scaled by its own head's `alpha`. The
  statistics are a head's, `heads * tile_heads` of them. The MXU does a head
  of 128's work for a head of 64 (its columns are 128 either way); the page's
  bytes are the heads' own. In the one-body rung the pair's queries lie ONE
  UNDER THE OTHER (`qh[0]`'s leading `2 * rows` rows, made at the block's
  first page), so both heads meet the row's keys in one product and its
  values in one more, where two products each loaded the same `[128, 128]`
  weights twice: a product's rows are independent, so every score and every
  sum is the one it was (1.33 -> 0.74 us a decode pair with the span, 0.90
  without; the same probe)."""
  pair = pl.program_id(0)
  i, lead = blk_ref[pair], page_ref[pair]   # its block, its first page
  (q_hbm, cols_ref, k_hbm, v_hbm, _, out_hbm, qb, qh, mb, lb, accb, k_scr,
   v_scr, sem, page_sem) = rest
  h = qb.shape[1] // heads
  nv = n_ref[i]
  walk_rung = ClearRung(rungs) if span > 1 else 0

  def _Span(k):
    """(table row, first logical page, live pages) of program k's span."""
    j, first_page = blk_ref[k], page_ref[k]
    pages = 1
    if walk_rung:
      pages = jnp.where(
          n_ref[j] <= walk_rung,
          jnp.minimum(last_ref[j] - first_page + 1, span), 1)
    return row_ref[j], first_page, pages

  def _Fetch(k, buf, wait: bool = False):
    """Starts (or awaits) the copies of program k's pages into half `buf` of
    the page scratch: slot s is logical page `first_page + s`, a LIVE page of
    the program's block, and a slot past the block's last is no copy at all."""
    row, first_page, pages = _Span(k)

    def _Slot(s):
      # a wait names its copy by shape alone
      pid = 0 if wait else tables_ref[row, first_page + s]
      for pool, scr in ((k_hbm, k_scr), (v_hbm, v_scr)):
        copy = pltpu.make_async_copy(pool.at[pid], scr.at[buf, s],
                                     page_sem.at[buf])
        copy.wait() if wait else copy.start()

    if walk_rung:
      jax.lax.fori_loop(0, pages, lambda s, c: (_Slot(s), c)[1], 0)
    else:
      _Slot(0)

  # The pools stay in HBM and a program's pages are copied by the program
  # before it: two halves of the scratch, one in use and one filling. The
  # first program starts its own.
  buf = jax.lax.rem(pair, 2)
  pl.when(pair == 0)(lambda: _Fetch(pair, buf))
  pl.when(pair + 1 < pairs_ref[0])(lambda: _Fetch(pair + 1, 1 - buf))
  _Fetch(pair, buf, wait=True)
  if tile_heads > 1:
    # which head of its row a lane is, over a row and over the block's width
    head_of = lambda width: (jax.lax.broadcasted_iota(
        jnp.int32, (1, width), 1) // (h // tile_heads)) % tile_heads
    lane_head, lane_head_all = head_of(h), head_of(heads * h)
  # a token's group is padded to whole sublane tiles (RaggedAttend), so a
  # block starts on one: the packed axis is the tiled one here
  first = pl.multiple_of(first_ref[i], SUBLANES)

  def _Copy(src, dst):
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()

  def _Block(rows):
    """The block's life over its pages, on the `rows` leading rows of every
    scratch (static): window in, accumulate, window out."""
    held = pl.ds(0, rows)
    window_q = pl.ds(first, rows)
    two_bodies = rows > ClearRung(rungs)
    # the rung with one body walks a span of its block's pages a program
    pages = jnp.minimum(last_ref[i] - lead + 1, span) if (
        walk_rung and not two_bodies) else 1
    # ... and, where a row of the pool holds a pair of heads, lays the pair's
    # queries one under the other: both meet the row's keys in ONE product
    stacked = pl.ds(0, rows * tile_heads)

    @pl.when(lead == page0_ref[i])
    def _Init():
      _Copy(q_hbm.at[window_q], qb.at[held])
      if tile_heads == 1:
        qh[held] = qb[held].astype(qh.dtype)
      elif two_bodies:
        for j in range(tile_heads):
          qh[j, held] = jnp.where(lane_head_all == j, qb[held],
                                  0.0).astype(qh.dtype)
      else:
        qh[0, stacked] = jnp.concatenate(
            [jnp.where(lane_head_all == j, qb[held], 0.0)
             for j in range(tile_heads)], axis=0).astype(qh.dtype)
      mb[:, held] = jnp.full((heads * tile_heads, rows, LANES), NEG_INF,
                             mb.dtype)
      lb[:, held] = jnp.zeros((heads * tile_heads, rows, LANES), lb.dtype)
      accb[held] = jnp.zeros((rows, heads * h), accb.dtype)

    def _Page(masked: bool):
      keep = None
      if masked:
        slot = lead * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)                     # [1, P]
        cols = cols_ref[0, held]                              # [rows, 4]
        keep = (slot < cols[:, 0:1]) & _AncestorOk(
            slot, slot - cols[:, 1:2], cols[:, 2:3], cols[:, 3:4])  # [rows, P]
        if window:
          keep &= slot >= cols[:, 0:1] - window
      keys, values = (_HeadPages(scr, heads, (buf, 0))
                      for scr in (k_scr, v_scr))
      # a clear page's statistics ride as the scratch holds them, lane-
      # replicated, where a page is as wide as they are
      stat = slice(None) if (not masked and page_size == LANES
                             and h % LANES == 0) else slice(1)
      for g in range(heads):
        lanes = pl.ds(g * h, h)
        if tile_heads > 1:
          acc_row = new_row = accb[held, lanes]
          for j in range(tile_heads):
            hd = g * tile_heads + j
            m, l, acc = _BlockPageAttend(
                qh[j, held, lanes], keys[g], values[g], keep,
                mb[hd, held, stat], lb[hd, held, stat], acc_row,
                (((1,), (1,)), ((), ())), (((1,), (0,)), ((), ())))
            mb[hd, held] = jnp.broadcast_to(m, (rows, LANES))
            lb[hd, held] = jnp.broadcast_to(l, (rows, LANES))
            new_row = jnp.where(lane_head == j, acc, new_row)
          accb[held, lanes] = new_row
          continue
        m, l, acc = _BlockPageAttend(
            qh[held, lanes], keys[g], values[g], keep, mb[g, held, stat],
            lb[g, held, stat], accb[held, lanes], (((1,), (1,)), ((), ())),
            (((1,), (0,)), ((), ())))
        mb[g, held] = jnp.broadcast_to(m, (rows, LANES))
        lb[g, held] = jnp.broadcast_to(l, (rows, LANES))
        accb[held, lanes] = acc

    def _Pages(count: int, s0=0):
      """`count` (static) consecutive pages of the program's span from slot
      `s0`, each through the masked body, in ONE basic block: the block's
      statistics and accumulator are read once, ride the pages as values and
      are written once, so what ties a page to the one before it is a maximum
      and two multiply-adds a head, and the pages' products can follow one
      another into the MXU without waiting for a softmax between them. Per
      (query, head, slot) the float operations and their order are `_Page`'s."""
      cols = cols_ref[0, held]                                # [rows, 4]
      every = heads * tile_heads
      ms = [mb[hd, held, :1] for hd in range(every)]
      ls = [lb[hd, held, :1] for hd in range(every)]
      accs = [accb[held, pl.ds(g * h, h)] for g in range(heads)]
      if tile_heads == 1:
        qs = [qh[held, pl.ds(g * h, h)] for g in range(heads)]
      else:
        qs = [qh[0, stacked, pl.ds(g * h, h)] for g in range(heads)]
      stack = lambda xs: jnp.concatenate(xs, axis=0)
      dims = ((((1,), (1,)), ((), ())), (((1,), (0,)), ((), ())))
      for s in range(count):
        slot = (lead + s0 + s) * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)                     # [1, P]
        keep = (slot < cols[:, 0:1]) & _AncestorOk(
            slot, slot - cols[:, 1:2], cols[:, 2:3], cols[:, 3:4])  # [rows, P]
        if window:
          keep &= slot >= cols[:, 0:1] - window
        keys, values = (_HeadPages(scr, heads, (buf, s0 + s))
                        for scr in (k_scr, v_scr))
        for g in range(heads):
          if tile_heads == 1:
            ms[g], ls[g], accs[g] = _BlockPageAttend(
                qs[g], keys[g], values[g], keep, ms[g], ls[g], accs[g], *dims)
            continue
          mine = range(g * tile_heads, (g + 1) * tile_heads)
          m, l, acc = _BlockPageAttend(
              qs[g], keys[g], values[g], stack([keep] * tile_heads),
              stack([ms[hd] for hd in mine]), stack([ls[hd] for hd in mine]),
              stack([accs[g]] * tile_heads), *dims)
          for j, hd in enumerate(mine):
            of_head = slice(j * rows, (j + 1) * rows)
            ms[hd], ls[hd] = m[of_head], l[of_head]
            accs[g] = jnp.where(lane_head == j, acc[of_head], accs[g])
      for hd in range(every):
        mb[hd, held] = jnp.broadcast_to(ms[hd], (rows, LANES))
        lb[hd, held] = jnp.broadcast_to(ls[hd], (rows, LANES))
      for g in range(heads):
        accb[held, pl.ds(g * h, h)] = accs[g]

    if two_bodies:
      is_clear = jnp.logical_and(lead >= clear_lo_ref[i], lead < clear_ref[i])
      pl.when(is_clear)(functools.partial(_Page, False))
      pl.when(jnp.logical_not(is_clear))(functools.partial(_Page, True))
    elif not walk_rung:
      _Pages(1)
    else:
      # a whole span in one block; a row's last, shorter one a page a trip
      pl.when(pages == span)(functools.partial(_Pages, span))

      @pl.when(pages < span)
      def _ShortSpan():
        jax.lax.fori_loop(0, pages, lambda s, c: (_Pages(1, s), c)[1], 0)

    @pl.when(lead + pages - 1 == last_ref[i])
    def _Emit():
      # a query of the rung's rows that is not this block's comes out an
      # exact zero, as in _RaggedAttendKernel: a masked page leaves it one by
      # itself (its `q_end` 0 masks every slot), a clear page does not
      if two_bodies:
        mine = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < nv
      for g in range(heads):
        lanes = pl.ds(g * h, h)
        out = _Finish(lb[g * tile_heads, held, :1], accb[held, lanes],
                      qb.dtype)
        for j in range(1, tile_heads):
          out = jnp.where(lane_head == j, _Finish(
              lb[g * tile_heads + j, held, :1], accb[held, lanes], qb.dtype),
                          out)
        if two_bodies:
          out = jnp.where(mine, out, jnp.zeros((), out.dtype))
        qb[held, lanes] = out
      _Copy(qb.at[held], out_hbm.at[window_q])

  # A rung's two bounds are two nested branches, not one `&`: what the block
  # does at every page (its accumulate) then stands two branches deep, as it
  # did under the dead-page guard, and the benchmark's host traces it there
  # in a third of the time it takes one branch deep (0.36-0.43 s a call of
  # this kernel against 1.1-1.3, 1.2 s of `setup_s`; PERF.md section 6,
  # PR 46). The program is the same.
  below = 0
  for rows in rungs:
    pl.when(nv > below)(functools.partial(
        pl.when(nv <= rows), functools.partial(_Block, rows)))
    below = rows


def _PairIndexMaps(minor: int):
  """(a page's index map, the `cols` one) of a grid over the plan's pairs.
  Program k names its own live page, `tables[row[blk[k]], page[k]]`, and
  nothing else: a stale table entry past a block's widest horizon, or behind
  its window, never reaches VMEM (the page-reuse-after-eviction guarantee).
  minor: the axes of a page's block after the first."""
  zeros = (0,) * minor

  def _PageIdx(k, blk_ref, page_ref, row_ref, last_ref, page0_ref, tables_ref,
               *_):
    return (tables_ref[row_ref[blk_ref[k]], page_ref[k]],) + zeros

  def _ColsIdx(k, blk_ref, *_):
    return (blk_ref[k], 0, 0)

  return _PageIdx, _ColsIdx


def _Prefetch(blocks: AttendPlan, tables) -> tuple:
  """What rides scalar prefetch into the attend kernels, in their order."""
  return (blocks.blk, blocks.page, blocks.row, blocks.last, blocks.page0,
          tables, blocks.n, blocks.first)


@functools.partial(jax.jit, static_argnames=(
    "page_size", "heads", "window", "rungs", "interpret", "tile_heads",
    "span"))
def _GroupedCall(pairs, prefetch, q, cols, k_pages, v_pages, *,
                 page_size: int, heads: int, window: int,
                 rungs: tuple[int, ...], interpret: bool,
                 tile_heads: int = 1, span: int = 1):
  """_GroupedAttendKernel over _PallasRaggedAttend's descriptors and the
  plan's list at `span` pages an entry of the one-body rung (`PlanKey.span`).
  pairs: [] the grid's length; q: [T + Bq, Nkv * H] f32; cols: [NB, Bq, 4];
  pages as rows [NP, P * Nkv, H] -> the output, [T + Bq, Nkv * H] f32, zeros
  where no block wrote. tile_heads 2: `heads` rows a token of two KV heads
  each, `[NP, P * Nkv / 2, 2 H]` (`TileHeads`).

  A `jit` of its own: a kernel's body is traced anew at every
  `pallas_call`, a rung of this one costs 0.4-0.6 s on the benchmark's host,
  and a period of four layers would pay that four times in set-up and again
  in every later program of the process. The layers of a stack that call at
  the same shapes share one trace. Only the call is inside: with the
  descriptors inside too, XLA no longer shared them between the layers of a
  step (0.66 ms a call; PERF.md section 6, PR 36). XLA names a kernel after
  the innermost scope round its call, which `jit` would make this function's
  name: the scope here keeps the name the callers' scope gives it."""
  bq = cols.shape[1]
  h = q.shape[1] // heads
  _, cols_idx = _PairIndexMaps(2)
  hbm = pl.BlockSpec(memory_space=pl.ANY)
  pages = (2, span, page_size * heads, h)    # two halves of `span` pages
  with observe.Scope("ragged_attend"):
    return pl.pallas_call(
        functools.partial(_GroupedAttendKernel, page_size=page_size,
                          window=window, heads=heads, rungs=rungs,
                          tile_heads=tile_heads, span=span),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch) + 1,
            grid=(pairs,),
            in_specs=[hbm, pl.BlockSpec((1, bq, 4), cols_idx), hbm, hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((bq, heads * h), jnp.float32),
                pltpu.VMEM(((tile_heads,) if tile_heads > 1 else ()) + (
                    bq, heads * h), k_pages.dtype),
                pltpu.VMEM((heads * tile_heads, bq, LANES), jnp.float32),
                pltpu.VMEM((heads * tile_heads, bq, LANES), jnp.float32),
                pltpu.VMEM((bq, heads * h), jnp.float32),
                pltpu.VMEM(pages, k_pages.dtype),
                pltpu.VMEM(pages, v_pages.dtype),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        input_output_aliases={len(prefetch) + 5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, jnp.reshape(pairs, (1,)), q, cols, k_pages, v_pages,
      jnp.zeros(q.shape, jnp.float32))


def _PallasRaggedAttend(q, k_pool, v_pool, block_tables, blocks: AttendPlan,
                        page_size: int, interpret: bool = False,
                        k_scale=None, v_scale=None, window: int = 0,
                        grouped: int = 0, span: int = 1):
  """Pallas lowering of _XlaRaggedAttend. q: [T, N, H] -> [T, N, H].

  blocks: the call's descriptors (BuildAttendPlan over the packed tokens
  and this table's shape, at this window).
  grouped (static): 0, or the queries a token lays on the packed axis where
  they are a KV head's group laid beside the tokens (RaggedAttend) and the
  pages float: the same grid, descriptors and page index map run
  _GroupedAttendKernel over pages seen as rows.

  span (static): `PlanKey.span` of the key `blocks` was built under, the
  pages an entry of its list stands for where the grouped kernel walks them.

  Grid `(blocks.pairs,)`, the step's live (block, page) pairs in order (a
  traced length: a step runs the programs it has work for, and none when it
  holds no block): block i + 1 starts where block i's queries end, so its
  window overwrites the zeros block i left past its own."""
  t, n, h = q.shape
  np_total, page, pool_heads, row = k_pool.shape
  assert page == page_size, (page, page_size)
  b = block_tables.shape[0]
  tables = jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1)
  nb, bq, _ = blocks.cols.shape
  assert nb == NumQueryBlocks(b, t, bq), (
      "descriptors of another pack", blocks.cols.shape, (b, t))
  prefetch = _Prefetch(blocks, tables) + blocks.col0
  if grouped:
    # the pool's own rows: a KV head each, or two side by side (TileHeads)
    out = _GroupedCall(
        blocks.pairs, prefetch + (blocks.clear_lo, blocks.clear),
        jnp.pad(q.reshape(t, n * h).astype(jnp.float32), ((0, bq), (0, 0))),
        blocks.cols, k_pool.reshape(np_total, page * pool_heads, row),
        v_pool.reshape(np_total, page * pool_heads, row), page_size=page_size,
        heads=pool_heads, window=window, rungs=BlockRungs(bq, grouped),
        interpret=interpret, tile_heads=n // pool_heads, span=span)
    return out[:t].astype(q.dtype).reshape(t, n, h)
  page_idx, cols_idx = _PairIndexMaps(3)
  hbm = pl.BlockSpec(memory_space=pl.ANY)
  in_specs = [
      hbm,
      pl.BlockSpec((1, bq, 4), cols_idx),
      pl.BlockSpec((1, page_size, n, h), page_idx),
      pl.BlockSpec((1, page_size, n, h), page_idx),
  ]
  # Bq rows of slack: the last block's window may run past T
  slack = ((0, bq), (0, 0), (0, 0))
  operands = [*prefetch, jnp.pad(q, slack), blocks.cols, k_pool, v_pool]
  if k_scale is not None:
    scale_idx = lambda k, *refs: page_idx(k, *refs)[:3]
    in_specs += [
        pl.BlockSpec((1, n, page_size), scale_idx),
        pl.BlockSpec((1, n, page_size), scale_idx),
    ]
    operands += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
  # the output starts as zeros and is written in place: a padding token is
  # in no block's window, or in the zeros past a block's own queries
  in_specs.append(hbm)
  operands.append(jnp.zeros((t + bq, n, h), q.dtype))

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=len(prefetch),
      grid=(blocks.pairs,),
      in_specs=in_specs,
      out_specs=hbm,
      scratch_shapes=[
          pltpu.VMEM((1, n, h), q.dtype),
          pltpu.VMEM((bq, n, h), q.dtype),
          pltpu.VMEM((n, LANES), jnp.float32),
          pltpu.VMEM((n, LANES), jnp.float32),
          pltpu.VMEM((n, h), jnp.float32),
          pltpu.VMEM((n, bq, LANES), jnp.float32),
          pltpu.VMEM((n, bq, LANES), jnp.float32),
          pltpu.VMEM((n, bq, h), jnp.float32),
          pltpu.SemaphoreType.DMA(()),
      ],
  )
  kernel = functools.partial(_RaggedAttendKernel, page_size=page_size,
                             window=window)
  out = pl.pallas_call(
      kernel,
      grid_spec=grid_spec,
      out_shape=jax.ShapeDtypeStruct((t + bq, n, h), q.dtype),
      input_output_aliases={len(operands) - 1: 0},
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("arbitrary",)),
      interpret=interpret,
  )(*operands)
  return out[:t]


# -- public entry ------------------------------------------------------------


def RaggedAttend(q, k_pool, v_pool, block_tables, row_of, q_end, *,
                 page_size: int, k_scale=None, v_scale=None,
                 q_start=None, anc_lo=None, anc_hi=None, window: int = 0,
                 lowering: str = "auto", interpret: bool | None = None,
                 plan=None):
  """Packed-token ragged paged attention — decode, prefill, and verify
  rows in one call.

  q: [T, N, H] packed query tokens, ALREADY scaled; every token's K/V was
  written to the pool before the call.
  k_pool/v_pool: [num_pages, page_size, Nkv, H] global page pool. Nkv
  divides N: query head n reads KV head n // (N // Nkv). A group of
  G = N // Nkv > 1 query heads becomes G more rows of M: a token's G
  queries of one KV head ride the packed axis as G consecutive queries of
  its row with its horizon, so both lowerings run [T * G, Nkv, H] queries
  against the Nkv heads they have, and a query block is Bq / G tokens by G
  heads. G == 1 is the call as it was, operand for operand.
  window: 0, or the slots a query sees counting its own: token t attends
  [q_end - window, q_end). Pages wholly behind a block's window are never
  read (the table's entries there may be stale).
  block_tables: [B, pages_per_seq] int32 physical page ids; entries past a
  row's live pages are arbitrary and never influence the output.
  row_of: [T] int32 — batch row (block-table index) of each token; a
  row's valid tokens are contiguous on the packed axis.
  q_end: [T] int32 — one past each token's highest attendable global slot
  (its `q_pos + 1`); 0 marks a padding token, whose output is 0.
  k_scale/v_scale: [num_pages, N, page_size] f32 sidecars for int8 pools
  (both or neither); pages dequantize in-kernel via `_DequantPages`.
  q_start/anc_lo/anc_hi: [T] int32 tree-speculation operands — q_start is
  the token's row step-window start (its row_q_pos) and anc_lo/anc_hi the
  64-bit ancestor-column bitmask; all three or none. None keeps chain
  semantics bitwise (every in-step predecessor visible).
  lowering: 'auto' (Pallas on real TPU, XLA twin elsewhere) | 'pallas' |
  'xla'.
  plan: None, or {PlanKey: AttendPlan} built over these same row_of / q_end
  / tree operands and this table's shape (BuildAttendPlan; a stack builds
  it once a step): the Pallas lowering takes the descriptors of its own
  key from it, and builds them itself when handed none. The twin takes
  none. Returns [T, N, H].
  """
  assert q.ndim == 3, q.shape
  assert (k_scale is None) == (v_scale is None), "pass both scales or neither"
  tree_args = (q_start is not None, anc_lo is not None, anc_hi is not None)
  assert all(tree_args) or not any(tree_args), \
      "pass q_start+anc_lo+anc_hi together or none"
  if k_scale is not None:
    assert k_pool.dtype == jnp.int8, k_pool.dtype
  t, n, h = q.shape
  # a token's row of the pool holds `tile` KV heads side by side (TileHeads)
  tile = k_pool.shape[3] // h
  assert k_pool.shape[3] == tile * h, (k_pool.shape, h)
  n_kv = k_pool.shape[2] * tile
  assert n % n_kv == 0, (n, n_kv)
  group = n // n_kv
  key = AttendPlanKey(n, n_kv, h, page_size, q.dtype, k_pool.dtype,
                      window=window, tree=q_start is not None,
                      lowering=lowering)
  # the grouped kernel wants every token's group to start on a sublane
  # tile: the group is padded with zero queries of the token's own horizon
  # (computed, dropped)
  grouped = key.kernel and Grouped(n, n_kv)
  if interpret is None:
    interpret = jax.default_backend() != "tpu"
  pool_heads = n_kv // tile
  # a token's row of the pool is whole lane tiles (the interpreter takes a
  # pair's row of any width: the tests' heads of 16)
  tiled = (tile * h) % LANES == 0 or (tile == 2 and interpret)
  if grouped and (k_scale is not None or not tiled or tile > 2 or (
      k_pool.dtype.itemsize == 2 and pool_heads > 1 and pool_heads % 2)):
    raise NotImplementedError(
        f"the Pallas lowering serves {n} query heads over {n_kv} KV heads "
        "from f32 pages, or bf16 pages of one or an even number of KV "
        "heads, whose heads tile the lanes, one a tile or (a head size of "
        f"64) two side by side in the pool's row; got {k_pool.dtype} pages "
        f"{tuple(k_pool.shape[2:])} a token, head size {h}" + (
            ", int8 scales" if k_scale is not None else ""))
  lanes = key.lanes
  if group > 1:
    # [T, Nkv, G, H] -> [T * G', Nkv, H]: the group beside the tokens
    q = q.reshape(t, n_kv, group, h).swapaxes(1, 2)
    q = jnp.pad(q, ((0, 0), (0, lanes - group), (0, 0), (0, 0)))
    q = q.reshape(-1, n_kv, h)
  if not key.kernel:
    tokens = [row_of, q_end]
    if q_start is not None:
      tokens += [q_start, anc_lo, anc_hi]
    tokens = [jnp.asarray(x) for x in tokens]
    if group > 1:
      tokens = [jnp.repeat(x, lanes) for x in tokens]
    if tile > 1:
      k_pool, v_pool = (x.reshape(x.shape[:2] + (n_kv, h))
                        for x in (k_pool, v_pool))
    out = _XlaRaggedAttend(q, k_pool, v_pool, block_tables, *tokens[:2],
                           page_size, k_scale, v_scale, *tokens[2:],
                           window=key.window)
  else:
    if plan is None:
      with observe.Scope("attend_descriptors"):
        blocks = BuildAttendPlan(
            key, row_of, q_end, q_start, anc_lo, anc_hi,
            b=block_tables.shape[0], t_pages=block_tables.shape[1])
    else:
      blocks = plan[key]
    out = _PallasRaggedAttend(
        q, k_pool, v_pool, block_tables, blocks, page_size,
        interpret=interpret, k_scale=k_scale, v_scale=v_scale,
        window=key.window, grouped=lanes if grouped else 0, span=key.span)
  if group > 1:
    out = out.reshape(t, lanes, n_kv, h)[:, :group]
    out = out.swapaxes(1, 2).reshape(t, n, h)
  return out
