"""The attend of latent attention (MLA) in its absorbed form, over a paged
pool of latent rows: decode rows and prompt chunks in one call.

What the pool keeps a token is ONE row of `W = kv_lora_rank +
qk_rope_head_dim` values, `[c_kv | k_r]` (core/mla.py), not K and V by heads.
In the absorbed form every query head carries a `W`-vector `[q_lat | q_rope]`
and scores it against that one row, and the value a head sums is the row's
first `V = kv_lora_rank` columns:

  s_i(t, s) = q_i(t) . row(s)          (q arrives PRE-SCALED)
  ctx_i(t)  = sum_s softmax_s(s_i) row(s)[:V]

So this is `ops/ragged_block_attend.py`'s grouped kernel with one KV head
whose group is ALL the query heads, a key of `W` and a value that is the
key's first `V` columns. The group rides the packed axis exactly as there (a
token's N heads are `lanes = Lanes(N)` consecutive queries of its row
with its horizon), and everything a step's rows decide is that module's: the
`PlanKey`, `BuildAttendPlan`'s query blocks and live (block, page) pairs,
the scalar prefetch, the page index map, `BlockRungs`. A stack builds the
descriptors once a step (core/attention.BuildRaggedPlan) and hands them to
every layer's call.

- `_LatentAttendKernel` (a TPU): grid = the step's live (block, page) pairs.
  A page `[P, W]` of the layer's pool comes into VMEM once a query block for
  all its heads, and only once: the value is a lane slice of the key's tile.
  Two plain products a page, `[rows, W] x [W, P]` and `[rows, P] x [P, V]`,
  f32 scores, f32 online softmax and accumulator, over the rung that holds the
  block's valid queries (a decode row's `lanes`, or Bq). q and the output cross
  HBM in the caller's dtype: a block starts at a multiple of `lanes` >= 16
  queries, a whole tile of either width.
- `_XlaLatentAttend` (elsewhere, and the twin the kernel is held to): a
  `fori_loop` over the batch's live pages, a token's page gathered through its
  row's table, the same arithmetic a (token, head).

A padding token (`q_end` 0) reads nothing and comes out an exact zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lingvo_tpu import observe
from lingvo_tpu.ops import ragged_block_attend as rba
from lingvo_tpu.ops.flash_attention import LANES, NEG_INF
from lingvo_tpu.ops.flash_decode import _Finish

SCOPE = "mla_attend"   # the device scope of both lowerings, and the kernel's
#                        name in a trace

# Queries a block holds: 32 tokens by 32 heads. The working set at W 320 (384
# lanes) and V 256 in bf16 is 5 MiB of the 16 a kernel may scope: q 0.75, the
# statistics 1, the accumulator 1, the output 0.5, the mask columns 1, a
# page twice 0.2, two score tiles 1.
_BQ = 1024


def Lanes(num_heads: int) -> int:
  """Queries a token lays on the packed axis where the kernel runs: its heads
  padded to whole 16-row tiles (a block then starts on a tile of f32 and of
  bf16 alike)."""
  return -(-num_heads // 16) * 16


def QueryBlock(num_heads: int) -> int:
  """Bq: whole tokens, at most `_BQ` queries."""
  lanes = Lanes(num_heads)
  return max(1, _BQ // lanes) * lanes


def PlanKey(num_heads: int, page_size: int, *, tree: bool = True,
            lowering: str = "auto") -> rba.PlanKey:
  """The ops/ragged_block_attend.PlanKey of a call with `num_heads` query
  heads: no window, a token's heads on the packed axis."""
  kernel = rba.Lowering(lowering) == "pallas"
  return rba.PlanKey(page_size, 0, QueryBlock(num_heads),
                     Lanes(num_heads) if kernel else num_heads, tree, kernel,
                     clear=kernel)


def SupportedOnTpu(page_size: int, value_dim: int) -> bool:
  """Whether Mosaic tiles the call: pages of whole sublane tiles and a value
  that ends on a lane tile of the row."""
  return page_size % 16 == 0 and value_dim % LANES == 0


# -- XLA twin (the CPU serving path) -----------------------------------------


def _XlaLatentAttend(q, pool, block_tables, row_of, q_end, page_size: int,
                     value_dim: int, q_start=None, anc_lo=None, anc_hi=None):
  """q [T, N, W]; pool [NP, P, W]; tables [B, t_pages]. -> [T, N, V]."""
  t, n, _ = q.shape
  np_total = pool.shape[0]
  t_pages = block_tables.shape[1]
  ends = q_end.astype(jnp.int32)
  if q_start is None:
    q_start = jnp.zeros((t,), jnp.int32)
    anc_lo = anc_hi = jnp.full((t,), -1, jnp.int32)
  starts, lo, hi = (x.astype(jnp.int32) for x in (q_start, anc_lo, anc_hi))
  trip = jnp.clip((jnp.max(ends) + page_size - 1) // page_size, 0, t_pages)
  tables = jnp.clip(block_tables.astype(jnp.int32), 0, np_total - 1)
  tok_tables = tables[jnp.clip(row_of.astype(jnp.int32), 0,
                               tables.shape[0] - 1)]          # [T, t_pages]

  def _Body(j, carry):
    m, l, acc = carry
    pid = jax.lax.dynamic_index_in_dim(tok_tables, j, axis=1, keepdims=False)
    page = pool[pid]                                          # [T, P, W]
    slot = j * page_size + jnp.arange(page_size, dtype=jnp.int32)
    keep = (slot[None, :] < ends[:, None]) & rba._AncestorOk(
        slot[None, :], slot[None, :] - starts[:, None], lo[:, None],
        hi[:, None])                                          # [T, P]
    return rba._BlockPageAttend(
        q, page, page[..., :value_dim], keep[:, None, :], m, l, acc,
        (((2,), (2,)), ((0,), (0,))), (((2,), (1,)), ((0,), (0,))))

  m0 = jnp.full((t, n, 1), NEG_INF, jnp.float32)
  l0 = jnp.zeros((t, n, 1), jnp.float32)
  acc0 = jnp.zeros((t, n, value_dim), jnp.float32)
  _, l, acc = jax.lax.fori_loop(0, trip, _Body, (m0, l0, acc0))
  return _Finish(l, acc, q.dtype)


# -- Pallas TPU kernel -------------------------------------------------------


def _LatentAttendKernel(blk_ref, page_ref, row_ref, last_ref, page0_ref,
                        tables_ref, n_ref, first_ref, clear_ref, q_hbm,
                        cols_ref, rows_ref, _, out_hbm, qb, mb, lb, accb, ob,
                        sem, *, page_size: int, value_dim: int, lanes: int,
                        rungs: tuple[int, ...]):
  """The (query block, logical page) program: ops/ragged_block_attend.
  _GroupedAttendKernel's for one KV head whose value is its key's first
  `value_dim` columns. q_hbm `[T * lanes + Bq, W]`, out_hbm `[T * lanes + Bq,
  V]`, rows_ref one page `[1, P, W]`. A block's life runs over the leading
  rows of every scratch that hold its valid queries (the first of `rungs`);
  rows past the rung are never written, and the output starts as zeros.

  A program does the vector work its page needs. The widest rung has two
  bodies. A page under `clear_ref[i]` lies whole under the horizon of every
  query of the block (`AttendPlan.clear`): its body reads no mask column,
  builds no mask and selects nothing, carries the softmax's statistics as the
  lane-replicated `[rows, 128]` the scratch holds (no slice in, no broadcast
  out; a page of 128 slots), and runs the rung's rows as two halves, two
  chains of products and vector passes with nothing between them, so one
  half's products run beside the other's passes. Any other page (the one or
  two the block's own tokens sit in) takes the masked body, over `[rows, 1]`
  statistics as it was. Both give the same bits: the same float ops in the
  same order a (query, slot). A masked page left the rung's rows that are not
  the block's at an exact zero by itself; a clear one does not, so `_Emit`
  zeroes them, once a block. A lower rung (a decode row) keeps the one masked
  body: its program is its fixed cost and its page's copy, and a second body
  is a second trace of the kernel in set-up. (PERF.md section 6, PR 55: what
  each of these bought, and what was tried and lost.)"""
  pair = pl.program_id(0)
  i, page = blk_ref[pair], page_ref[pair]
  nv = n_ref[i]
  first = pl.multiple_of(first_ref[i], lanes)

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
      mb[held] = jnp.full((rows, LANES), NEG_INF, mb.dtype)
      lb[held] = jnp.zeros((rows, LANES), lb.dtype)
      accb[held] = jnp.zeros((rows, value_dim), accb.dtype)

    def _Page(masked: bool):
      # the statistics as the scratch holds them, where a page is as wide
      wide = (not masked and page_size == LANES and value_dim % LANES == 0
              and rows % (2 * lanes) == 0)
      for r in ((pl.ds(0, rows // 2), pl.ds(rows // 2, rows // 2)) if wide
                else (held,)):
        keep = None
        if masked:
          slot = page * page_size + jax.lax.broadcasted_iota(
              jnp.int32, (1, page_size), 1)                   # [1, P]
          cols = cols_ref[0, r]                               # [rows, 4]
          keep = (slot < cols[:, 0:1]) & rba._AncestorOk(
              slot, slot - cols[:, 1:2], cols[:, 2:3], cols[:, 3:4])
        stat = (lambda ref: ref[r]) if wide else (lambda ref: ref[r, :1])
        m, l, acc = rba._BlockPageAttend(
            qb[r], rows_ref[0], rows_ref[0, :, :value_dim], keep,
            stat(mb), stat(lb), accb[r],
            (((1,), (1,)), ((), ())), (((1,), (0,)), ((), ())))
        mb[r] = jnp.broadcast_to(m, (r.size, LANES))
        lb[r] = jnp.broadcast_to(l, (r.size, LANES))
        accb[r] = acc

    if rows > rba.ClearRung(rungs):
      is_clear = page < clear_ref[i]
      pl.when(is_clear)(functools.partial(_Page, False))
      pl.when(jnp.logical_not(is_clear))(functools.partial(_Page, True))
    else:
      _Page(True)

    @pl.when(page == last_ref[i])
    def _Emit():
      # a query of the rung's rows that is not this block's comes out an
      # exact zero (the block's own lead its window)
      mine = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < nv
      ob[held] = jnp.where(
          mine, _Finish(lb[held, :1], accb[held], ob.dtype),
          jnp.zeros((), ob.dtype))
      _Copy(ob.at[held], out_hbm.at[window_q])

  # two nested branches a rung, as _GroupedAttendKernel's (and for its
  # reason: the host traces the body there in a third of the time)
  below = 0
  for rows in rungs:
    pl.when(nv > below)(functools.partial(
        pl.when(nv <= rows), functools.partial(_Block, rows)))
    below = rows


@functools.partial(jax.jit, static_argnames=(
    "page_size", "value_dim", "lanes", "rungs", "interpret"))
def _LatentCall(pairs, prefetch, q, cols, pages, *, page_size: int,
                value_dim: int, lanes: int, rungs: tuple[int, ...],
                interpret: bool):
  """_LatentAttendKernel over the plan's grid. pairs: [] the grid's length;
  q `[T * lanes + Bq, W]`; cols `[NB, Bq, 4]`; pages `[NP, P, W]` -> `[T *
  lanes + Bq, V]` in q's dtype, zeros where no block wrote. A `jit` of its
  own, as ops/ragged_block_attend._GroupedCall is and for its reasons: the
  layers of a stack share one trace of the kernel's body, and the scope here
  keeps the kernel's name."""
  bq = cols.shape[1]
  w = q.shape[1]
  page_idx, cols_idx = rba._PairIndexMaps(2)
  hbm = pl.BlockSpec(memory_space=pl.ANY)
  out_shape = (q.shape[0], value_dim)
  with observe.Scope(SCOPE):
    return pl.pallas_call(
        functools.partial(_LatentAttendKernel, page_size=page_size,
                          value_dim=value_dim, lanes=lanes, rungs=rungs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(pairs,),
            in_specs=[
                hbm,
                pl.BlockSpec((1, bq, 4), cols_idx),
                pl.BlockSpec((1, page_size, w), page_idx),
                hbm,
            ],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((bq, w), q.dtype),
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, value_dim), jnp.float32),
                pltpu.VMEM((bq, value_dim), q.dtype),
                pltpu.SemaphoreType.DMA(()),
            ]),
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        input_output_aliases={len(prefetch) + 3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, q, cols, pages, jnp.zeros(out_shape, q.dtype))


# -- public entry ------------------------------------------------------------


def LatentAttend(q, pool, block_tables, row_of, q_end, *, page_size: int,
                 value_dim: int, q_start=None, anc_lo=None, anc_hi=None,
                 lowering: str = "auto", interpret: bool | None = None,
                 plan=None):
  """q `[T, N, W]` packed queries in the absorbed form, ALREADY scaled (a
  head's `[q_lat | q_rope]`); pool `[num_pages, page_size, W]`, every token's
  row written before the call; `value_dim` the leading columns of a row that
  are its value. block_tables / row_of / q_end / the tree operands / plan as
  ops/ragged_block_attend.RaggedAttend takes them (`plan[PlanKey(...)]` are
  this call's descriptors). Returns the context `[T, N, value_dim]`."""
  t, n, w = q.shape
  assert pool.ndim == 3 and pool.shape[1:] == (page_size, w), (
      pool.shape, page_size, w)
  assert 0 < value_dim <= w, (value_dim, w)
  tree = q_start is not None
  assert tree == (anc_lo is not None) == (anc_hi is not None)
  if lowering == "auto" and not SupportedOnTpu(page_size, value_dim):
    lowering = "xla"
  key = PlanKey(n, page_size, tree=tree, lowering=lowering)
  if not key.kernel:
    with observe.Scope(SCOPE):
      return _XlaLatentAttend(q, pool, block_tables, row_of, q_end,
                              page_size, value_dim, q_start, anc_lo, anc_hi)
  if plan is None:
    with observe.Scope("attend_descriptors"):
      blocks = rba.BuildAttendPlan(
          key, row_of, q_end, q_start, anc_lo, anc_hi,
          b=block_tables.shape[0], t_pages=block_tables.shape[1])
  else:
    blocks = plan[key]
  if interpret is None:
    interpret = jax.default_backend() != "tpu"
  lanes, bq = key.lanes, key.bq
  nb = blocks.cols.shape[0]
  assert nb == rba.NumQueryBlocks(block_tables.shape[0], t * lanes, bq), (
      "descriptors of another pack", blocks.cols.shape)
  tables = jnp.clip(block_tables.astype(jnp.int32), 0, pool.shape[0] - 1)
  # [T, N, W] -> [T * lanes + Bq, W]: a token's heads beside it, padded to
  # whole tiles with zero queries of its horizon (computed, dropped), and Bq
  # rows of slack past T for the last block's window
  laid = jnp.pad(q, ((0, 0), (0, lanes - n), (0, 0))).reshape(t * lanes, w)
  out = _LatentCall(
      blocks.pairs, rba._Prefetch(blocks, tables) + (blocks.clear,),
      jnp.pad(laid, ((0, bq), (0, 0))), blocks.cols, pool,
      page_size=page_size, value_dim=value_dim, lanes=lanes,
      rungs=rba.BlockRungs(bq, lanes), interpret=interpret)
  return out[:t * lanes].reshape(t, lanes, value_dim)[:, :n]
