"""Differential attention over the serving engine's pages (arXiv:2410.05258).

Query heads come in pairs `(2j, 2j + 1)`, K heads in pairs `(2i, 2i + 1)`
with `i = j // (pairs / K pairs)`, and V is read as one head of twice the
head size a K pair, `V_i = [v_2i; v_2i+1]`:

    a1 = softmax(q_2j   k_2i^T),   a2 = softmax(q_2j+1 k_2i+1^T)
    o_j = (a1 - lam * a2) V_i = a1 V_i - lam * (a2 V_i)

Both softmaxes are causal, and windowed where the layer is. q arrives
pre-scaled.

As grouped-query attention: a K pair `[k_2i; k_2i+1]` is one vector of 2H
numbers a token, and so is `V_i`. A query head padded with zeros to 2H (its
own half kept: the first for `q_2j`, the second for `q_2j+1`) has with that
vector exactly its score against its own K head. So the 2 * pairs padded
queries over the Nk / 2 wide heads are plain grouped-query attention with two
softmaxes a pair, each with its own output `a V_i`; the difference of a
pair's two outputs is taken after it. Both lowerings run it so:

- `_XlaDiffAttend`, the CPU serving path and the twin: ops/
  ragged_block_attend's XLA lowering over the pool seen `[NP, P, Nk / 2,
  2H]`.
- `_PallasDiffAttend`, the chip's. A pool `[NP, P, Nk, H]` with H under the
  lane width lies on the chip with the TOKENS on the lanes (the compiler's
  layout of that shape: a page is `[Nk, H, P]` there, and any view with H or
  2H on the lanes is a copy of the whole pool, every layer). So the kernel
  takes a page as it lies, `[Nk * H, P]`: rows `[2H * i, 2H * (i + 1))` are
  `[k_2i; k_2i+1]^T`, the K pair transposed, whole tiles. A block's scores
  are then the plain product `[rows, 2H] x [2H, P]` and its output `[rows,
  P] x [2H, P]^T`, with no strided load and nothing re-laid out. Block
  descriptors (one `rba.AttendPlan` a step for the layers of one window,
  handed in as `plan`), the grid (one program a live (block, page) pair of
  the plan's list, as many as the step holds), the block rungs and the `jit`
  round the call are ops/ragged_block_attend's grouped kernel's; the
  kernel's name in a trace is `diff_attend`.

What the padded form costs beside a kernel that kept H-wide queries and
subtracted inside: the zero half of every score product, and twice the
output rows across HBM (PERF.md section 7 sizes both).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lingvo_tpu import observe
from lingvo_tpu.ops import ragged_block_attend as rba
from lingvo_tpu.ops.flash_attention import LANES, NEG_INF, SUBLANES
from lingvo_tpu.ops.flash_decode import _Finish


def PaddedQueries(q):
  """[T, Nq, H] -> [T, Nq, 2H]: an even head in the first half, an odd one
  in the second, zeros in the other."""
  even = (jnp.arange(q.shape[1]) % 2 == 0)[None, :, None]
  zero = jnp.zeros_like(q)
  return jnp.concatenate([jnp.where(even, q, zero),
                          jnp.where(even, zero, q)], axis=-1)


def WidePages(pool):
  """[NP, P, Nk, H] -> [NP, P, Nk / 2, 2H]: a pair of heads as one."""
  np_total, page, nk, h = pool.shape
  assert nk % 2 == 0, pool.shape
  return pool.reshape(np_total, page, nk // 2, 2 * h)


def SupportedOnTpu(page_size: int, h: int) -> bool:
  """Mosaic's tiling: a page's tokens on whole lanes, a K pair's 2H rows on
  whole 16-bit sublane tiles."""
  return page_size % LANES == 0 and (2 * h) % (2 * SUBLANES) == 0


def _XlaDiffAttend(q2, k_pool, v_pool, block_tables, row_of, q_end,
                   page_size: int, window: int):
  return rba.RaggedAttend(
      q2, WidePages(k_pool), WidePages(v_pool), block_tables, row_of, q_end,
      page_size=page_size, window=window, lowering="xla")


def _TransposedPagesKernel(blk_ref, page_ref, row_ref, last_ref, page0_ref,
                           tables_ref, n_ref, first_ref, *rest,
                           page_size: int, window: int, heads: int,
                           rungs: tuple[int, ...]):
  """The live (query block, logical page) pair's program of
  rba._GroupedAttendKernel over pages that lie transposed, `[heads * h, P]`:
  head g's keys are the rows `[g * h, (g + 1) * h)`, whole. q and the output
  `[T' + Bq, heads * h]` f32 in HBM, a head's queries a lane slice; a block
  runs the first of `rungs` that holds its valid queries."""
  pair = pl.program_id(0)
  i, page = blk_ref[pair], page_ref[pair]
  q_hbm, cols_ref, k_ref, v_ref, _, out_hbm, qb, qh, mb, lb, accb, sem = rest
  h = qb.shape[1] // heads
  nv = n_ref[i]
  first = pl.multiple_of(first_ref[i], SUBLANES)

  def _Copy(src, dst):
    cp = pltpu.make_async_copy(src, dst, sem)
    cp.start()
    cp.wait()

  def _Block(rows):
    held = pl.ds(0, rows)
    window_q = pl.ds(first, rows)

    @pl.when(page == page0_ref[i])
    def _Init():
      _Copy(q_hbm.at[window_q], qb.at[held])
      qh[held] = qb[held].astype(qh.dtype)
      mb[:, held] = jnp.full((heads, rows, LANES), NEG_INF, mb.dtype)
      lb[:, held] = jnp.zeros((heads, rows, LANES), lb.dtype)
      accb[held] = jnp.zeros((rows, heads * h), accb.dtype)

    def _Accumulate():
      slot = page * page_size + jax.lax.broadcasted_iota(
          jnp.int32, (1, page_size), 1)                       # [1, P]
      ends = cols_ref[0, held][:, 0:1]                        # [rows, 1]
      keep = slot < ends
      if window:
        keep &= slot >= ends - window
      for g in range(heads):
        lanes = pl.ds(g * h, h)
        m, l, acc = rba._BlockPageAttend(
            qh[held, lanes], k_ref[0, lanes, :], v_ref[0, lanes, :], keep,
            mb[g, held, :1], lb[g, held, :1], accb[held, lanes],
            (((1,), (0,)), ((), ())), (((1,), (1,)), ((), ())))
        mb[g, held] = jnp.broadcast_to(m, (rows, LANES))
        lb[g, held] = jnp.broadcast_to(l, (rows, LANES))
        accb[held, lanes] = acc

    _Accumulate()

    @pl.when(page == last_ref[i])
    def _Emit():
      for g in range(heads):
        lanes = pl.ds(g * h, h)
        qb[held, lanes] = _Finish(lb[g, held, :1], accb[held, lanes],
                                  qb.dtype)
      _Copy(qb.at[held], out_hbm.at[window_q])

  # nested, not `&`: rba._GroupedAttendKernel says why
  below = 0
  for rows in rungs:
    pl.when(nv > below)(functools.partial(
        pl.when(nv <= rows), functools.partial(_Block, rows)))
    below = rows


@functools.partial(jax.jit, static_argnames=(
    "page_size", "heads", "window", "rungs", "interpret"))
def _TransposedCall(pairs, prefetch, q, cols, k_pages, v_pages, *,
                    page_size: int, heads: int, window: int,
                    rungs: tuple[int, ...], interpret: bool):
  """_TransposedPagesKernel over the plan's `pairs` live pairs. q:
  [T' + Bq, heads * h] f32; cols: [NB, Bq, 4]; pages [NP, heads * h, P]. A
  `jit` of its own and the scope inside it, as rba._GroupedCall and for its
  reasons."""
  bq = cols.shape[1]
  h = q.shape[1] // heads
  page_idx, cols_idx = rba._PairIndexMaps(2)
  hbm = pl.BlockSpec(memory_space=pl.ANY)
  with observe.Scope("diff_attend"):
    return pl.pallas_call(
        functools.partial(_TransposedPagesKernel, page_size=page_size,
                          window=window, heads=heads, rungs=rungs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(pairs,),
            in_specs=[
                hbm,
                pl.BlockSpec((1, bq, 4), cols_idx),
                pl.BlockSpec((1, heads * h, page_size), page_idx),
                pl.BlockSpec((1, heads * h, page_size), page_idx),
                hbm,
            ],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((bq, heads * h), jnp.float32),
                pltpu.VMEM((bq, heads * h), k_pages.dtype),
                pltpu.VMEM((heads, bq, LANES), jnp.float32),
                pltpu.VMEM((heads, bq, LANES), jnp.float32),
                pltpu.VMEM((bq, heads * h), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        input_output_aliases={len(prefetch) + 4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, q, cols, k_pages, v_pages, jnp.zeros(q.shape, jnp.float32))


def AsItLies(pool):
  """[NP, P, Nk, H] -> [NP, Nk * H, P]: on the chip the same bytes."""
  np_total, page, nk, h = pool.shape
  return pool.transpose(0, 2, 3, 1).reshape(np_total, nk * h, page)


def _FromAsItLies(pages, nk: int):
  np_total, rows, page = pages.shape
  return pages.reshape(np_total, nk, rows // nk, page).transpose(0, 3, 1, 2)


def _Words(x):
  """[rows, P] -> the same bytes as 32-bit words (Mosaic rolls no other): a
  16-bit page's sublane pairs, its lanes where they were."""
  return x if x.dtype.itemsize == 4 else pltpu.bitcast(x, jnp.uint32)


def _WriteKernel(page_ref, tok0_ref, lane0_ref, lane1_ref, tiles_ref, k_old,
                 v_old, k_new, v_new, k_out, v_out, k_lanes, v_lanes):
  """Pair i's program: page `page_ref[i]` of both pools with the lanes
  `[lane0, lane1)` taken from the packed tokens `tok0 ..`, every other lane
  the old page's. The step's new K and V arrive as they are, `[T, Nk * H]`
  whole in VMEM; the first program lays the tokens the step holds out on the
  lanes once, a tile of P tokens a transpose (`k_lanes`, `v_lanes`: `[Nk * H,
  P + T + P]`, token t at lane P + t), and a pair's page is a window of that
  rolled to its first lane. Moves and selects only: a bit pattern lands as
  it left."""
  i = pl.program_id(0)
  page = k_old.shape[2]

  @pl.when(i == 0)
  def _LayOut():
    def _Tile(g, _):
      at = pl.multiple_of(g * page, page)
      for new, lanes in ((k_new, k_lanes), (v_new, v_lanes)):
        lanes[:, pl.ds(at + page, page)] = new[pl.ds(at, page), :].T

    jax.lax.fori_loop(0, tiles_ref[0], _Tile, None)

  lane0, lane1 = lane0_ref[i], lane1_ref[i]
  # lane j of the page is lane `at + j` of the laid-out tokens: two aligned
  # windows, each lane from the one that holds it, rolled down by `at % P`
  at = tok0_ref[i] - lane0 + page
  first = pl.multiple_of((at // page) * page, page)
  shift = at - first
  lane = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
  keep = (lane >= lane0) & (lane < lane1)
  for old, lanes, out in ((k_old, k_lanes, k_out), (v_old, v_lanes, v_out)):
    window = jnp.where(lane >= shift, _Words(lanes[:, pl.ds(first, page)]),
                       _Words(lanes[:, pl.ds(first + page, page)]))
    new = pltpu.roll(window, (page - shift) % page, 1)
    out[0] = pltpu.bitcast(jnp.where(keep, new, _Words(old[0])), out.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _WriteCall(pairs, prefetch, k_pages, v_pages, k_new, v_new, *,
               interpret: bool):
  """_WriteKernel over the step's `pairs` live (row, page) pairs (a traced
  grid length, as rba._GroupedCall's: a step runs the programs it has pages
  for). prefetch: (page ids, first packed token, first lane, last lane a
  pair, [the tiles of P tokens the step holds]); pages `[NP, Nk * H, P]`, as
  the pool lies; new `[T', Nk * H]`, T' whole tiles. A `jit` of its own and
  the scope inside it, as rba._GroupedCall and for its reasons."""
  _, rows, page = k_pages.shape
  by_page = lambda i, ids, *_: (ids[i], 0, 0)
  block = pl.BlockSpec((1, rows, page), by_page)
  whole = pl.BlockSpec(memory_space=pltpu.VMEM)
  lanes = pltpu.VMEM((rows, k_new.shape[0] + 2 * page), k_pages.dtype)
  with observe.Scope("kv_write"):     # inside the jit: the kernel's name
    return pl.pallas_call(
        _WriteKernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(pairs,),
            in_specs=[block, block, whole, whole],
            out_specs=[block, block],
            scratch_shapes=[lanes, lanes]),
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        input_output_aliases={len(prefetch): 0, len(prefetch) + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, k_pages, v_pages, k_new, v_new)


def PageWrites(b: int, t: int, page_size: int) -> int:
  """Static bound on the (row, page) pairs a step writes: a row of `len`
  tokens touches one page and one more for every page boundary it crosses,
  under 2 + len / P, and a row of one token one."""
  return min(t, 2 * b + t // page_size)


class WritePlan(NamedTuple):
  """The (row, logical page) pairs a step writes, rows in slot order, the
  live ones first (NW = `PageWrites` of them, static; the kernel's grid runs
  the first `pairs`), and where a pair's new lanes come from: all of it the
  step's rows' alone, none of it an owner's."""
  r: jnp.ndarray      # [NW] int32 the pair's row
  lp: jnp.ndarray     # [NW] int32 its logical page, inside the table
  live: jnp.ndarray   # [NW] bool; a dead pair runs no program
  tok0: jnp.ndarray   # [NW] int32 the packed token its first new lane takes
  lane0: jnp.ndarray  # [NW] int32 the page's first lane the step writes
  lane1: jnp.ndarray  # [NW] int32 and the lane after its last
  pairs: jnp.ndarray  # [] int32 the live pairs
  tiles: jnp.ndarray  # [1] int32 the tiles of P packed tokens the rows reach


def BuildWritePlan(rows, b: int, t_pages: int, page: int) -> WritePlan:
  """rows: the step's core/ragged.RaggedRows; block tables [b, t_pages] of
  pages of `page` tokens."""
  t = rows.row_of.shape[0]
  p0 = rows.row_q_pos.astype(jnp.int32)
  n = rows.row_len.astype(jnp.int32)
  col0 = rows.row_cols[:, 0].astype(jnp.int32)
  first_page = p0 // page
  n_pages = jnp.where(n > 0, (p0 + n - 1) // page - first_page + 1, 0)
  cum = jnp.cumsum(n_pages)
  i = jnp.arange(PageWrites(b, t, page), dtype=jnp.int32)
  r = jnp.clip(jnp.searchsorted(cum, i, side="right"), 0, b - 1)
  lp = first_page[r] + i - (cum[r] - n_pages[r])
  live = i < cum[-1]
  # the slots of the page the row's tokens fill, [lo, hi); a dead pair's
  # are read by no program
  lo = jnp.maximum(p0[r], lp * page)
  hi = jnp.minimum((p0 + n)[r], (lp + 1) * page)
  reach = jnp.max(jnp.where(n > 0, col0 + n, 0))
  return WritePlan(
      r=r, lp=jnp.clip(lp, 0, t_pages - 1), live=live,
      tok0=col0[r] + lo - p0[r], lane0=lo - lp * page, lane1=hi - lp * page,
      pairs=cum[-1],
      tiles=jnp.clip(-(-reach // page), 0, -(-t // page))[None])


def WritePages(k_pool, v_pool, k_new, v_new, block_tables, rows, *,
               lowering: str = "auto", interpret: bool | None = None,
               plan: WritePlan | None = None):
  """Every valid token's K and V `[T, Nk, H]` into its row's page at its
  slot (`rows`: the step's core/ragged.RaggedRows; block_tables [B,
  t_pages]); padding tokens write nothing, or the pool's last page. ->
  (k_pool, v_pool).

  The XLA lowering scatters rows of `[Nk, H]`. On the chip a token is a
  LANE of its page (module docstring), and a scatter there re-lays the whole
  pool out, so the kernel rewrites whole pages: a program a (row, page) pair
  the step writes and no other (`WritePlan.pairs` of them; a page the step
  does not write, the trash page among them, is not touched), which lays
  the new tokens out itself (`_WriteKernel`).
  plan: the step's BuildWritePlan over these rows and this table's shape (a
  stack builds it once for all its owners); the kernel's call builds its own
  when handed none. The scatter takes none."""
  lowering = rba.Lowering(lowering)
  np_total, page, nk, h = k_pool.shape
  b, t_pages = block_tables.shape
  t = k_new.shape[0]
  tables = jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1)
  if lowering == "xla":
    with observe.Scope("kv_layout"):
      pos = rows.pos.astype(jnp.int32)
      row = jnp.clip(rows.row_of.astype(jnp.int32), 0, b - 1)
      logical = jnp.clip(pos // page, 0, t_pages - 1)
      phys = jnp.where(rows.valid, tables[row, logical], np_total - 1)
      off = jnp.where(rows.valid, pos % page,
                      jnp.arange(t, dtype=jnp.int32) % page)
    return (k_pool.at[phys, off].set(k_new.astype(k_pool.dtype)),
            v_pool.at[phys, off].set(v_new.astype(v_pool.dtype)))
  with observe.Scope("kv_layout"):
    if plan is None:
      plan = BuildWritePlan(rows, b, t_pages, page)
    prefetch = (tables[plan.r, plan.lp], plan.tok0, plan.lane0, plan.lane1,
                plan.tiles)

    def _Tokens(new):
      # whole tiles of P tokens: the pad is the pack's, not a page's
      return jnp.pad(new.reshape(t, nk * h).astype(k_pool.dtype),
                     ((0, -t % page), (0, 0)))

    operands = (AsItLies(k_pool), AsItLies(v_pool), _Tokens(k_new),
                _Tokens(v_new))
  if interpret is None:
    interpret = jax.default_backend() != "tpu"
  # the write itself stays outside `kv_layout`: its kernel is `kv_write`
  k_pages, v_pages = _WriteCall(plan.pairs, prefetch, *operands,
                                interpret=interpret)
  with observe.Scope("kv_layout"):
    return _FromAsItLies(k_pages, nk), _FromAsItLies(v_pages, nk)


def DiffPlanKey(nq: int, nk: int, h: int, page_size: int, q_dtype, kv_dtype,
                *, window: int = 0, lowering: str = "auto") -> rba.PlanKey:
  """The rba.PlanKey of DiffAttend called with `nq` query heads over `nk` K
  heads of size `h`: grouped-query attention of 2H-wide queries over Nk / 2
  wide heads, chains only. Its kernel runs one body a rung, reads no `clear`
  and runs a program a page (`span` 1)."""
  return rba.AttendPlanKey(
      nq, nk // 2, 2 * h, page_size, q_dtype, kv_dtype, window=window,
      tree=False, lowering=lowering)._replace(clear=False, span=1)


def _PallasDiffAttend(q2, k_pool, v_pool, block_tables, blocks: rba.AttendPlan,
                      page_size: int, window: int, interpret: bool):
  """q2: [T, N, 2H] padded queries -> [T, N, 2H], a softmax's `a V` each.
  blocks: the call's descriptors (rba.BuildAttendPlan at DiffPlanKey)."""
  t, n, h2 = q2.shape
  np_total, page, nk, h = k_pool.shape
  assert page == page_size and h2 == 2 * h, (k_pool.shape, q2.shape)
  heads = nk // 2
  group = n // heads
  lanes = rba.GroupLanes(group)
  b = block_tables.shape[0]
  nb, bq, _ = blocks.cols.shape
  assert nb == rba.NumQueryBlocks(b, t * lanes, bq), (
      "descriptors of another pack", blocks.cols.shape, (b, t, lanes))
  tables = jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1)
  with observe.Scope("diff_layout"):
    # the group beside the tokens, padded to whole sublane tiles
    # (RaggedAttend)
    q = q2.reshape(t, heads, group, h2).swapaxes(1, 2)
    q = jnp.pad(q, ((0, 0), (0, lanes - group), (0, 0), (0, 0)))
    q = q.reshape(t * lanes, heads * h2).astype(jnp.float32)
    operands = (jnp.pad(q, ((0, bq), (0, 0))), blocks.cols, AsItLies(k_pool),
                AsItLies(v_pool))
  # the call stays outside: its kernel is `diff_attend`
  out = _TransposedCall(
      blocks.pairs, rba._Prefetch(blocks, tables), *operands,
      page_size=page_size, heads=heads, window=window,
      rungs=rba.BlockRungs(bq, lanes), interpret=interpret)
  with observe.Scope("diff_layout"):
    out = out[:t * lanes].reshape(t, lanes, heads, h2)[:, :group]
    return out.swapaxes(1, 2).reshape(t, n, h2)


def DiffAttend(q, k_pool, v_pool, block_tables, row_of, q_end, lam, *,
               page_size: int, window: int = 0, lowering: str = "auto",
               interpret: bool | None = None, plan=None):
  """q: [T, 2 * pairs, H] packed queries, scaled; pools [NP, P, Nk, H];
  block_tables [B, t_pages]; row_of / q_end [T] as RaggedAttend's (a row's
  tokens contiguous, q_end 0 = padding); lam: the layer's scalar.
  lowering: 'auto' (the kernel on a TPU, the twin elsewhere) | 'pallas' |
  'xla'. plan: as RaggedAttend's, {rba.PlanKey: rba.AttendPlan} over these
  row_of / q_end and this table's shape, from which the kernel's call takes
  the descriptors of its DiffPlanKey; it builds them itself when handed
  none. -> [T, pairs, 2H] in q's dtype, zeros at padding tokens."""
  t, nq, h = q.shape
  key = DiffPlanKey(nq, k_pool.shape[2], h, page_size, q.dtype, k_pool.dtype,
                    window=window, lowering=lowering)
  with observe.Scope("diff_layout"):
    q2 = PaddedQueries(q)
  if not key.kernel:
    out = _XlaDiffAttend(q2, k_pool, v_pool, block_tables, row_of, q_end,
                         page_size, key.window)
  else:
    if plan is None:
      with observe.Scope("diff_descriptors"):
        blocks = rba.BuildAttendPlan(
            key, row_of, q_end, b=block_tables.shape[0],
            t_pages=block_tables.shape[1])
    else:
      blocks = plan[key]
    out = _PallasDiffAttend(
        q2, k_pool, v_pool, block_tables, blocks, page_size, key.window,
        interpret=(jax.default_backend() != "tpu") if interpret is None
        else interpret)
  with observe.Scope("diff_layout"):
    out = out.astype(jnp.float32).reshape(t, nq // 2, 2, 2 * h)
    return (out[:, :, 0] - lam * out[:, :, 1]).astype(q.dtype)
