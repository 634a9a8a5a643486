"""ops/ragged_block_attend.py: the unified ragged kernel's twin contract.

The op that collapses decode / chunked prefill / spec-verify into one
program must hold the same guarantees each specialized op held:
- XLA twin == Pallas(interpret) within `_ATOL` (5e-6 in f32), including
  dead-page clamp, q_len=1 degenerate rows, q_end=0 padding tokens, and page
  reuse after a real allocator eviction. The kernel runs a block of a row's
  queries against a page in one product where the twin runs one query, so
  the interpreter's dots sum in another order and the twins agree to
  rounding, not to the bit (measured: 2.4e-7); what IS bitwise is stated
  where it is asserted (padding zeros, hostile tables, chain sentinels);
- a row's valid tokens are CONTIGUOUS on the packed axis (the op's
  contract since the blocked kernel; padding may sit anywhere);
- stale block-table entries (freed/foreign pages) never leak into output;
- an all-decode token pack reproduces `BlockDecode` bit for bit and a
  prefill pack reproduces `BlockPrefill` (same `_PageAttend` float-op
  sequence) — the "three programs become views of one op" claim, at the
  op level;
- the int8 path dequantizes on read through the shared `_DequantPages`: the
  int8 XLA twin is bitwise the float twin on dequantized pools.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lingvo_tpu.core import ragged
from lingvo_tpu.ops import block_decode
from lingvo_tpu.ops import ragged_block_attend
from lingvo_tpu.quant import kv as kv_quant
from lingvo_tpu.serving import kv_cache


def _QuantizePools(k_pool, v_pool):
  k8, ks = kv_quant.QuantizeKv(jnp.asarray(k_pool))
  v8, vs = kv_quant.QuantizeKv(jnp.asarray(v_pool))
  return (k8, jnp.swapaxes(ks, 1, 2).astype(jnp.float32),
          v8, jnp.swapaxes(vs, 1, 2).astype(jnp.float32))


_ATOL = 5e-6


class TestRaggedAttend:

  def _Inputs(self, b=3, t_pages=2, page=8, n=1, h=8, t=8, seed=0):
    rng = np.random.RandomState(seed)
    np_total = b * t_pages + 1
    q = rng.randn(t, n, h).astype(np.float32)
    k_pool = rng.randn(np_total, page, n, h).astype(np.float32)
    v_pool = rng.randn(np_total, page, n, h).astype(np.float32)
    tables = rng.permutation(np_total - 1).reshape(b, t_pages).astype(
        np.int32)
    return q, k_pool, v_pool, tables

  @staticmethod
  def _DenseRef(q, k_pool, v_pool, tables, row_of, q_end):
    """numpy masked softmax per packed token over its row's gathered view."""
    t, n, h = q.shape
    out = np.zeros_like(q)
    for ti in range(t):
      end = int(q_end[ti])
      if end == 0:
        continue
      row = int(row_of[ti])
      k = k_pool[tables[row]].reshape(-1, n, h)[:end]
      v = v_pool[tables[row]].reshape(-1, n, h)[:end]
      s = np.einsum("nh,snh->ns", q[ti], k)
      s = s - s.max(axis=-1, keepdims=True)
      p = np.exp(s)
      p /= p.sum(axis=-1, keepdims=True)
      out[ti] = np.einsum("ns,snh->nh", p, v)
    return out

  def _Both(self, q, kp, vp, tables, row_of, q_end, page=8, **kw):
    out_x = ragged_block_attend.RaggedAttend(
        jnp.asarray(q), kp, vp, jnp.asarray(tables), jnp.asarray(row_of),
        jnp.asarray(q_end), page_size=page, lowering="xla", **kw)
    out_p = ragged_block_attend.RaggedAttend(
        jnp.asarray(q), kp, vp, jnp.asarray(tables), jnp.asarray(row_of),
        jnp.asarray(q_end), page_size=page, lowering="pallas",
        interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               rtol=0, atol=_ATOL)
    return np.asarray(out_x)

  def test_mixed_rows_match_dense_reference(self):
    """One pack spanning the full row spectrum: a q_len=1 decode token, a
    3-token prefill chunk, a 3-token verify window, and a padding token."""
    q, k_pool, v_pool, tables = self._Inputs()
    #       decode row0 | prefill row1 (slots 4,5,6) | verify row2 | pad
    row_of = np.array([0, 1, 1, 1, 2, 2, 2, 0], np.int32)
    q_end = np.array([9, 5, 6, 7, 12, 13, 14, 0], np.int32)
    out = self._Both(q, jnp.asarray(k_pool), jnp.asarray(v_pool), tables,
                     row_of, q_end)
    ref = self._DenseRef(q, k_pool, v_pool, tables, row_of, q_end)
    np.testing.assert_allclose(out, ref, atol=5e-6)
    # the padding token is exactly zero, not NaN
    np.testing.assert_array_equal(out[7], np.zeros_like(out[7]))

  def test_stale_table_entries_never_leak(self):
    """Table entries past a token's horizon may point anywhere (freed or
    foreign pages); they must not change the output."""
    q, k_pool, v_pool, tables = self._Inputs()
    row_of = np.array([0, 0, 1, 1, 1, 2, 2, 2], np.int32)
    q_end = np.array([3, 4, 5, 6, 7, 2, 3, 4], np.int32)  # page 1 dead
    out1 = self._Both(q, jnp.asarray(k_pool), jnp.asarray(v_pool), tables,
                      row_of, q_end)
    hostile = tables.copy()
    hostile[:, 1] = [tables[1, 0], tables[2, 0], tables[0, 0]]  # alias
    out2 = self._Both(q, jnp.asarray(k_pool), jnp.asarray(v_pool), hostile,
                      row_of, q_end)
    np.testing.assert_array_equal(out1, out2)

  def test_all_decode_pack_bitwise_equals_block_decode(self):
    """T tokens with one token per row reproduce BlockDecode exactly —
    the decode program was already this op."""
    q, k_pool, v_pool, tables = self._Inputs(b=3, t=3)
    lens = np.array([5, 16, 1], np.int32)
    row_of = np.arange(3, dtype=np.int32)
    out_r = self._Both(q, jnp.asarray(k_pool), jnp.asarray(v_pool), tables,
                       row_of, lens)
    out_b = block_decode.BlockDecode(
        jnp.asarray(q)[:, None], jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lens), page_size=8, lowering="xla")
    np.testing.assert_array_equal(out_r, np.asarray(out_b)[:, 0])

  def test_prefill_pack_bitwise_equals_block_prefill(self):
    """A packed prefill chunk reproduces BlockPrefill exactly — causal
    masking within the chunk is just each token's shorter horizon."""
    q, k_pool, v_pool, tables = self._Inputs(b=2, t=6)
    q_pos = np.array([2, 8], np.int32)
    in_len = np.array([3, 3], np.int32)
    row_of = np.array([0, 0, 0, 1, 1, 1], np.int32)
    q_end = np.array([3, 4, 5, 9, 10, 11], np.int32)   # q_pos + c + 1
    out_r = self._Both(q, jnp.asarray(k_pool), jnp.asarray(v_pool), tables,
                       row_of, q_end)
    out_p = block_decode.BlockPrefill(
        jnp.asarray(q).reshape(2, 3, 1, 8), jnp.asarray(k_pool),
        jnp.asarray(v_pool), jnp.asarray(tables), jnp.asarray(q_pos),
        jnp.asarray(in_len), page_size=8)
    np.testing.assert_allclose(out_r.reshape(2, 3, 1, 8), np.asarray(out_p),
                               atol=5e-6)

  def test_twins_bitwise_equal_incl_page_reuse(self):
    """XLA == Pallas(interpret) (within _ATOL) before AND after a real allocator
    frees one sequence's pages and hands them to another (pool bytes
    overwritten in place — exactly what eviction + admission does)."""
    q, k_pool, v_pool, tables = self._Inputs(b=2, t=5)
    k_pool = jnp.asarray(k_pool)
    v_pool = jnp.asarray(v_pool)
    row_of = np.array([0, 1, 1, 1, 0], np.int32)
    q_end = np.array([5, 14, 15, 16, 0], np.int32)
    self._Both(q, k_pool, v_pool, tables, row_of, q_end)

    alloc = kv_cache.PageAllocator(num_pages=4, page_size=8)
    alloc.Allocate("a", 2)
    alloc.Allocate("b", 2)
    alloc.Free("a")
    reused = alloc.Allocate("c", 2)
    assert reused == [0, 1]
    rng = np.random.RandomState(7)
    for pg in reused:
      k_pool = k_pool.at[pg].set(rng.randn(8, 1, 8).astype(np.float32))
      v_pool = v_pool.at[pg].set(rng.randn(8, 1, 8).astype(np.float32))
    tables2 = np.array([reused, list(alloc.PagesOf("b"))], np.int32)
    q_end2 = np.array([10, 12, 14, 15, 16], np.int32)
    row_of2 = np.array([0, 0, 1, 1, 1], np.int32)
    out = self._Both(q, k_pool, v_pool, tables2, row_of2, q_end2)
    ref = self._DenseRef(q, np.asarray(k_pool), np.asarray(v_pool),
                         tables2, row_of2, q_end2)
    np.testing.assert_allclose(out, ref, atol=5e-6)

  def test_int8_twins_bitwise_and_match_float_on_dequant(self):
    """int8 XLA == int8 Pallas(interpret) within _ATOL, and the XLA twin
    is bitwise the float
    kernel run on elementwise-dequantized pools: dequantize-on-read is the
    ONLY thing the quantized path adds."""
    q, k_pool, v_pool, tables = self._Inputs()
    k8, ks, v8, vs = _QuantizePools(k_pool, v_pool)
    kf = kv_quant.DequantKv(k8.swapaxes(1, 2), ks).swapaxes(1, 2)
    vf = kv_quant.DequantKv(v8.swapaxes(1, 2), vs).swapaxes(1, 2)
    row_of = np.array([0, 1, 1, 1, 2, 2, 2, 0], np.int32)
    q_end = np.array([9, 5, 6, 7, 12, 13, 14, 0], np.int32)
    out_q = self._Both(q, k8, v8, tables, row_of, q_end,
                       k_scale=ks, v_scale=vs)
    out_f = ragged_block_attend.RaggedAttend(
        jnp.asarray(q), kf, vf, jnp.asarray(tables), jnp.asarray(row_of),
        jnp.asarray(q_end), page_size=8, lowering="xla")
    np.testing.assert_array_equal(out_q, np.asarray(out_f))

  @pytest.mark.slow
  def test_twin_sweep_over_horizon_grid(self):
    """Twin equality across horizon grids incl. 0, 1, and capacity."""
    q, k_pool, v_pool, tables = self._Inputs(b=4, t_pages=2, t=4)
    row_of = np.arange(4, dtype=np.int32)
    for ends in ([0, 1, 8, 16], [16, 16, 16, 16], [0, 0, 0, 0],
                 [7, 9, 15, 3]):
      self._Both(q, jnp.asarray(k_pool), jnp.asarray(v_pool), tables,
                 row_of, np.asarray(ends, np.int32))

  def test_supported_on_tpu_gate_is_shared(self):
    assert ragged_block_attend.SupportedOnTpu(128, 128)
    assert not ragged_block_attend.SupportedOnTpu(8, 128)
    assert not ragged_block_attend.SupportedOnTpu(128, 8)


class TestAncestorMaskedAttend:
  """Per-token in-step ancestor visibility (tree speculation).

  A tree token's horizon is its causal window MINUS in-window slots that
  are not on its root path: slot s is visible iff s < q_end and (s below
  the row's step window, or bit (s - q_start) of the token's ancestor
  mask is set). Chain rows ship the -1/-1 sentinel masks and must stay
  BITWISE the unmasked kernel."""

  _Inputs = TestRaggedAttend._Inputs
  _Both = TestRaggedAttend._Both

  @staticmethod
  def _TreeRow(q_pos, parents):
    """Per-token (q_end, q_start, lo, hi) for one DFS-packed tree row."""
    lo, hi = ragged.TreeAncestorMasks(parents)
    n = len(parents) + 1
    q_end = q_pos + 1 + np.arange(n)          # own DFS slot inclusive
    q_start = np.full((n,), q_pos, np.int32)
    return q_end.astype(np.int32), q_start, lo, hi

  @staticmethod
  def _MaskedDenseRef(q, k_pool, v_pool, tables, row_of, q_end, q_start,
                      lo, hi):
    t, n, h = q.shape
    out = np.zeros_like(q)
    for ti in range(t):
      end = int(q_end[ti])
      if end == 0:
        continue
      mask = (np.int64(np.uint32(lo[ti]))
              | (np.int64(np.uint32(hi[ti])) << 32))
      slots = np.arange(end)
      c = np.clip(slots - int(q_start[ti]), 0, 63)
      keep = ((mask >> c) & 1).astype(bool)
      kk = k_pool[tables[int(row_of[ti])]].reshape(-1, n, h)[:end][keep]
      vv = v_pool[tables[int(row_of[ti])]].reshape(-1, n, h)[:end][keep]
      s = np.einsum("nh,snh->ns", q[ti], kk)
      s = s - s.max(axis=-1, keepdims=True)
      p = np.exp(s)
      p /= p.sum(axis=-1, keepdims=True)
      out[ti] = np.einsum("ns,snh->nh", p, vv)
    return out

  def test_tree_row_matches_masked_dense_reference(self):
    """A w=2,k=2 tree row next to a plain decode row: each tree token
    sees the committed prefix + its own root path, never its siblings;
    XLA == Pallas(interpret) within _ATOL throughout."""
    q, k_pool, v_pool, tables = self._Inputs()
    parents = [-1, 0, -1, 2]
    t_end, t_start, t_lo, t_hi = self._TreeRow(6, parents)
    row_of = np.array([0] * 5 + [1, 0, 0], np.int32)
    q_end = np.concatenate([t_end, [9, 0, 0]]).astype(np.int32)
    q_start = np.concatenate([t_start, [0, 0, 0]]).astype(np.int32)
    lo = np.concatenate([t_lo, [-1, -1, -1]]).astype(np.int32)
    hi = np.concatenate([t_hi, [-1, -1, -1]]).astype(np.int32)
    out = self._Both(q, jnp.asarray(k_pool), jnp.asarray(v_pool), tables,
                     row_of, q_end, q_start=q_start, anc_lo=lo, anc_hi=hi)
    ref = self._MaskedDenseRef(q, k_pool, v_pool, tables, row_of, q_end,
                               q_start, lo, hi)
    np.testing.assert_allclose(out, ref, atol=5e-6)
    # the two branches are built over the same prefix but must differ
    # (each excludes the other's slots); padding stays exactly zero
    assert not np.array_equal(out[2], out[4])
    np.testing.assert_array_equal(out[7], np.zeros_like(out[7]))

  def test_chain_sentinels_bitwise_equal_unmasked(self):
    """-1/-1 masks with any q_start reproduce the unmasked kernel BIT FOR
    BIT on a mixed decode/prefill/verify pack — the no-regression proof
    for every pre-tree serving shape."""
    q, k_pool, v_pool, tables = self._Inputs()
    row_of = np.array([0, 1, 1, 1, 2, 2, 2, 0], np.int32)
    q_end = np.array([9, 5, 6, 7, 12, 13, 14, 0], np.int32)
    q_start = np.array([8, 2, 2, 2, 9, 9, 9, 0], np.int32)
    neg = np.full((8,), -1, np.int32)
    base = self._Both(q, jnp.asarray(k_pool), jnp.asarray(v_pool), tables,
                      row_of, q_end)
    masked = self._Both(q, jnp.asarray(k_pool), jnp.asarray(v_pool),
                        tables, row_of, q_end, q_start=q_start,
                        anc_lo=neg, anc_hi=neg)
    np.testing.assert_array_equal(base, masked)

  def test_masked_twins_bitwise_incl_page_reuse(self):
    """XLA == Pallas(interpret) within _ATOL on ancestor-masked packs before
    AND after a real allocator eviction hands one row's pages to another
    (the _Both helper asserts the twin equality on every call)."""
    q, k_pool, v_pool, tables = self._Inputs(b=2, t=5)
    k_pool = jnp.asarray(k_pool)
    v_pool = jnp.asarray(v_pool)
    parents = [-1, 0, 1, -1]                     # a 3-chain + 1 sibling
    t_end, t_start, lo5, hi5 = self._TreeRow(8, parents)
    row_of = np.array([0] * 5, np.int32)
    self._Both(q, k_pool, v_pool, tables, row_of, t_end,
               q_start=t_start, anc_lo=lo5, anc_hi=hi5)
    alloc = kv_cache.PageAllocator(num_pages=4, page_size=8)
    alloc.Allocate("a", 2)
    alloc.Allocate("b", 2)
    alloc.Free("a")
    reused = alloc.Allocate("c", 2)
    rng = np.random.RandomState(7)
    for pg in reused:
      k_pool = k_pool.at[pg].set(rng.randn(8, 1, 8).astype(np.float32))
      v_pool = v_pool.at[pg].set(rng.randn(8, 1, 8).astype(np.float32))
    tables2 = np.array([reused, list(alloc.PagesOf("b"))], np.int32)
    out = self._Both(q, k_pool, v_pool, tables2, row_of, t_end,
                     q_start=t_start, anc_lo=lo5, anc_hi=hi5)
    ref = self._MaskedDenseRef(q, np.asarray(k_pool), np.asarray(v_pool),
                               tables2, row_of, t_end, t_start, lo5, hi5)
    np.testing.assert_allclose(out, ref, atol=5e-6)

  def test_int8_masked_twins_bitwise(self):
    """The int8 path composes with ancestor masks: quantized XLA ==
    quantized Pallas(interpret) within _ATOL, the XLA twin bitwise the float kernel on
    dequantized pools."""
    q, k_pool, v_pool, tables = self._Inputs()
    k8, ks, v8, vs = _QuantizePools(k_pool, v_pool)
    kf = kv_quant.DequantKv(k8.swapaxes(1, 2), ks).swapaxes(1, 2)
    vf = kv_quant.DequantKv(v8.swapaxes(1, 2), vs).swapaxes(1, 2)
    parents = [-1, 0, -1, 2]
    t_end, t_start, t_lo, t_hi = self._TreeRow(6, parents)
    row_of = np.array([0] * 5 + [1, 1, 1], np.int32)
    q_end = np.concatenate([t_end, [5, 6, 7]]).astype(np.int32)
    q_start = np.concatenate([t_start, [4, 4, 4]]).astype(np.int32)
    lo = np.concatenate([t_lo, [-1, -1, -1]]).astype(np.int32)
    hi = np.concatenate([t_hi, [-1, -1, -1]]).astype(np.int32)
    out_q = self._Both(q, k8, v8, tables, row_of, q_end, k_scale=ks,
                       v_scale=vs, q_start=q_start, anc_lo=lo, anc_hi=hi)
    out_f = ragged_block_attend.RaggedAttend(
        jnp.asarray(q), kf, vf, jnp.asarray(tables), jnp.asarray(row_of),
        jnp.asarray(q_end), page_size=8, lowering="xla",
        q_start=jnp.asarray(q_start), anc_lo=jnp.asarray(lo),
        anc_hi=jnp.asarray(hi))
    np.testing.assert_array_equal(out_q, np.asarray(out_f))


class TestQueryBlocks:
  """The blocked kernel where it can go wrong and the per-token one could
  not: blocks of one row's queries cut at Bq, at any packed offset.

  Pages of 16, so `QueryBlock` gives Bq 16 and a 37-token row spans three
  blocks. Every case holds Pallas(interpret) to the XLA twin and to the
  dense numpy reference within `_ATOL` (5e-6; the blocked products sum in
  another order than the twin's one-query ones), and padding tokens to
  exact zeros."""

  PAGE, T_PAGES, B, N, H = 16, 4, 6, 2, 8

  # name -> (packed width, [(row, q_pos, tokens)] in packed order,
  #          padding tokens before the first row)
  PACKS = {
      "chunk_spans_blocks_ragged_last": (48, [(0, 9, 37), (1, 30, 1)], 0),
      "row_starts_mid_block_after_one_token_row":
          (40, [(0, 20, 1), (1, 3, 20), (2, 40, 1)], 0),
      "decode_only": (8, [(r, 5 + 9 * r, 1) for r in range(6)], 0),
      "empty_slots_between_live_rows":
          (40, [(0, 7, 1), (2, 0, 18), (5, 33, 2)], 0),
      "all_padding_tail": (64, [(1, 12, 17), (3, 50, 1)], 0),
      "all_padding_pack": (24, [], 0),
      "horizons_straddle_page_boundary":
          (24, [(0, 10, 12), (4, 28, 8)], 0),
      "padding_before_and_between_rows": (40, [(1, 2, 19), (2, 44, 3)], 5),
      "full_blocks_exact": (40, [(0, 0, 32), (3, 15, 1)], 0),
  }

  @classmethod
  def _Pools(cls, seed=0):
    rng = np.random.RandomState(seed)
    np_total = cls.B * cls.T_PAGES + 1
    shape = (np_total, cls.PAGE, cls.N, cls.H)
    tables = rng.permutation(np_total - 1).reshape(
        cls.B, cls.T_PAGES).astype(np.int32)
    return (rng.randn(*shape).astype(np.float32),
            rng.randn(*shape).astype(np.float32), tables)

  @classmethod
  def _Pack(cls, name, seed=1):
    t, rows, lead = cls.PACKS[name]
    rng = np.random.RandomState(seed)
    q = rng.randn(t, cls.N, cls.H).astype(np.float32)
    row_of = np.zeros((t,), np.int32)
    q_end = np.zeros((t,), np.int32)
    q_start = np.zeros((t,), np.int32)
    cursor = lead
    for i, (row, q_pos, n) in enumerate(rows):
      cursor += bool(lead and i)  # and a padding token between its rows
      sl = slice(cursor, cursor + n)
      row_of[sl] = row
      q_end[sl] = q_pos + 1 + np.arange(n)
      q_start[sl] = q_pos
      cursor += n
    assert cursor <= t, (cursor, t)
    return q, row_of, q_end, q_start

  @classmethod
  def _Run(cls, lowering, q, kp, vp, tables, row_of, q_end, **kw):
    kw = {k: jnp.asarray(v) for k, v in kw.items()}
    extra = dict(interpret=True) if lowering == "pallas" else {}
    return np.asarray(ragged_block_attend.RaggedAttend(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(row_of), jnp.asarray(q_end),
        page_size=cls.PAGE, lowering=lowering, **extra, **kw))

  def test_block_size_comes_from_shapes(self):
    qb = ragged_block_attend.QueryBlock
    assert qb(self.N, self.H, self.PAGE, np.float32, np.float32) == 16
    # the serving shapes of dense1b fit a page of queries in every dtype;
    # twice the heads in f32 pass the VMEM budget and the block halves
    assert qb(16, 128, 128, jnp.bfloat16, jnp.bfloat16) == 128
    assert qb(16, 128, 128, jnp.bfloat16, jnp.int8) == 128
    assert qb(16, 128, 128, np.float32, np.float32) == 128
    assert qb(32, 128, 128, np.float32, np.float32) == 32
    assert ragged_block_attend.NumQueryBlocks(32, 544, 128) == 36

  @pytest.mark.parametrize("name", sorted(PACKS))
  def test_blocked_kernel_matches_twin_and_dense(self, name):
    kp, vp, tables = self._Pools()
    q, row_of, q_end, _ = self._Pack(name)
    out_p = self._Run("pallas", q, kp, vp, tables, row_of, q_end)
    out_x = self._Run("xla", q, kp, vp, tables, row_of, q_end)
    ref = TestRaggedAttend._DenseRef(q, kp, vp, tables, row_of, q_end)
    np.testing.assert_allclose(out_p, out_x, rtol=0, atol=_ATOL)
    np.testing.assert_allclose(out_p, ref, rtol=0, atol=_ATOL)
    # bitwise: a padding token reads exact zeros, whatever its neighbours
    pad = q_end == 0
    np.testing.assert_array_equal(out_p[pad], np.zeros_like(out_p[pad]))
    assert np.all(np.isfinite(out_p))

  @pytest.mark.parametrize("name", ["chunk_spans_blocks_ragged_last",
                                    "horizons_straddle_page_boundary",
                                    "decode_only"])
  def test_hostile_entries_past_last_live_page(self, name):
    """Table entries past a row's last live page point at another row's
    live pages and at the trash page; the kernel's output is BITWISE what
    it is with its own tables (same ops on the same pages)."""
    kp, vp, tables = self._Pools()
    q, row_of, q_end, _ = self._Pack(name)
    clean = self._Run("pallas", q, kp, vp, tables, row_of, q_end)
    hostile = tables.copy()
    for row in range(self.B):
      ends = q_end[(row_of == row) & (q_end > 0)]
      live = 0 if ends.size == 0 else -(-int(ends.max()) // self.PAGE)
      for j in range(live, self.T_PAGES):
        hostile[row, j] = (tables[(row + 1) % self.B, 0] if j % 2
                           else self.B * self.T_PAGES)
    out = self._Run("pallas", q, kp, vp, hostile, row_of, q_end)
    np.testing.assert_array_equal(out, clean)

  @pytest.mark.parametrize("name", ["chunk_spans_blocks_ragged_last",
                                    "row_starts_mid_block_after_one_token_row"])
  def test_int8_pools(self, name):
    """Quantized pools through the blocked body: Pallas(interpret) against
    the int8 twin, which is bitwise the float twin on dequantized pools."""
    kp, vp, tables = self._Pools()
    q, row_of, q_end, _ = self._Pack(name)
    k8, ks, v8, vs = _QuantizePools(kp, vp)
    out_p = self._Run("pallas", q, k8, v8, tables, row_of, q_end,
                      k_scale=ks, v_scale=vs)
    out_x = self._Run("xla", q, k8, v8, tables, row_of, q_end,
                      k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(out_p, out_x, rtol=0, atol=_ATOL)

  @pytest.mark.parametrize("tree_len", [5, 21])
  def test_tree_row_beside_chain_rows(self, tree_len):
    """A DFS-packed tree row (inside one block at 5 columns, over two at
    21) between a decode row and a prefill chunk: each tree token sees the
    committed prefix and its own root path only."""
    kp, vp, tables = self._Pools()
    rng = np.random.RandomState(3)
    parents = [-1] + [int(rng.randint(-1, j)) for j in range(1, tree_len - 1)]
    lo_t, hi_t = ragged.TreeAncestorMasks(parents)
    rows = [(0, 30, 1), (2, 11, tree_len), (4, 2, 19)]
    t = 48
    q = rng.randn(t, self.N, self.H).astype(np.float32)
    row_of = np.zeros((t,), np.int32)
    q_end = np.zeros((t,), np.int32)
    q_start = np.zeros((t,), np.int32)
    lo = np.full((t,), -1, np.int32)
    hi = np.full((t,), -1, np.int32)
    cursor = 0
    for row, q_pos, n in rows:
      sl = slice(cursor, cursor + n)
      row_of[sl], q_start[sl] = row, q_pos
      q_end[sl] = q_pos + 1 + np.arange(n)
      if row == 2:
        lo[sl], hi[sl] = lo_t, hi_t
      cursor += n
    kw = dict(q_start=q_start, anc_lo=lo, anc_hi=hi)
    out_p = self._Run("pallas", q, kp, vp, tables, row_of, q_end, **kw)
    out_x = self._Run("xla", q, kp, vp, tables, row_of, q_end, **kw)
    ref = TestAncestorMaskedAttend._MaskedDenseRef(
        q, kp, vp, tables, row_of, q_end, q_start, lo, hi)
    np.testing.assert_allclose(out_p, out_x, rtol=0, atol=_ATOL)
    np.testing.assert_allclose(out_p, ref, rtol=0, atol=_ATOL)
    np.testing.assert_array_equal(out_p[cursor:],
                                  np.zeros_like(out_p[cursor:]))


class TestGroupedClearPages:
  """`_GroupedAttendKernel`'s two bodies of the widest rung: a page in the
  plan's `clear_lo <= page < clear` runs no mask, and the output is BITWISE
  what the masked body gives at every page."""

  # rows: a decode row deep in its context, an empty slot, a chunk that starts
  # mid-page and ends in a partial block, a short chunk (or a 13-node tree)
  LENS, Q_POS = [1, 0, 100, 13], [650, 9, 700, 40]
  PARENTS = [-1, 0, 0, 2, -1, 4, 4, 6, -1, 8, 9, 9]
  T, H, SLOTS = 120, 128, 1024

  def _Case(self, heads, page, window, tree):
    n, nk = heads
    t_pages = self.SLOTS // page
    b = len(self.LENS)
    rows = ragged.BuildRaggedRows(
        np.array(self.LENS), np.array(self.Q_POS), self.T, 128,
        row_parents={3: self.PARENTS} if tree else None)
    rows = ragged.RaggedRows(*(jnp.asarray(m) for m in rows))
    tok = ragged.BuildTokenView(rows, b, t_pages, page)
    rng = np.random.RandomState(5)
    np_total = b * t_pages + 1
    tables = rng.permutation(np_total - 1).reshape(b, t_pages).astype(np.int32)
    # every page no query may see is poison: behind a row's window, past its
    # horizon, and the pages no table names
    seen = np.zeros((np_total,), bool)
    for r, (n_r, pos) in enumerate(zip(self.LENS, self.Q_POS)):
      if n_r:
        lo = max(pos + 1 - window, 0) // page if window else 0
        seen[tables[r, lo:(pos + n_r - 1) // page + 1]] = True
    kp, vp = (jnp.asarray(np.where(seen[:, None, None, None],
                                   rng.randn(np_total, page, nk, self.H),
                                   np.nan), jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.randn(self.T, n, self.H) * self.H ** -0.5, jnp.float32)
    key = ragged_block_attend.AttendPlanKey(
        n, nk, self.H, page, q.dtype, kp.dtype, window=window,
        lowering="pallas")
    tree_kw = dict(q_start=tok.q_start, anc_lo=rows.anc_lo,
                   anc_hi=rows.anc_hi)
    blocks = ragged_block_attend.BuildAttendPlan(
        key, tok.row, tok.q_end, *tree_kw.values(), b=b, t_pages=t_pages)

    def _Call(lowering, kp=kp, vp=vp, **kw):
      return np.asarray(ragged_block_attend.RaggedAttend(
          q, kp, vp, jnp.asarray(tables), tok.row, tok.q_end, page_size=page,
          window=window, lowering=lowering, **tree_kw, **kw))

    return key, blocks, rows, tok, t_pages, _Call, (kp, vp)

  @pytest.mark.parametrize("tree", [False, True], ids=["chains", "tree"])
  @pytest.mark.parametrize("window", [0, 300])
  @pytest.mark.parametrize("page", [16, 128])
  @pytest.mark.parametrize("heads", [(8, 2), (32, 2), (16, 8)],
                           ids=["group4", "group16", "eight_kv_heads"])
  def test_clear_pages_are_bitwise_the_masked_pages(self, heads, page, window,
                                                    tree):
    key, blocks, rows, tok, t_pages, call, pools = self._Case(
        heads, page, window, tree)
    assert key.clear and key.bq == 512 and key.lanes in (8, 16)
    n, last, lo, hi = (np.asarray(x) for x in (
        blocks.n, blocks.last, blocks.clear_lo, blocks.clear))
    wide = n > ragged_block_attend.ClearRung(
        ragged_block_attend.BlockRungs(key.bq, key.lanes))
    clear_pairs = np.where(wide, np.maximum(np.minimum(hi, last + 1) - lo, 0),
                           0)
    # the chunk's blocks (the last one partial) hold clear pages; with a
    # window some of the block's pages lie behind the range as well
    assert int(np.sum(clear_pairs > 0)) >= 2 and int(np.sum(wide)) >= 3
    if not tree:
      assert int(clear_pairs.sum()) == ragged_block_attend.ClearPairs(
          key, rows.row_q_pos, rows.row_len, t_pages)
    if window:
      assert np.any(lo[wide] > np.asarray(blocks.page0)[wide])
    out = call("pallas", interpret=True, plan={key: blocks})
    masked = call("pallas", interpret=True, plan={
        key: blocks._replace(clear=jnp.zeros_like(blocks.clear))})
    assert np.all(np.isfinite(out))
    np.testing.assert_array_equal(out, masked)
    np.testing.assert_array_equal(out[np.asarray(tok.q_end) == 0], 0.0)
    # and the comparison sees a page that runs unmasked and is not clear
    wrong = call("pallas", interpret=True, plan={
        key: blocks._replace(clear=blocks.clear + 1)})
    assert not np.array_equal(wrong, out)
    # the twin masks what it gathers, and a masked NaN is still one
    clean = [jnp.nan_to_num(x) for x in pools]
    np.testing.assert_allclose(out, call("xla", *clean), rtol=0, atol=2e-5)

  def test_the_grouped_key_asks_and_the_other_kernels_keys_do_not(self):
    """`clear` is a function of shapes: set where the Pallas lowering runs
    the grouped kernel, for no head-batched call, no twin's and not for
    differential attention's kernel, which reads none."""
    from lingvo_tpu.ops import diff_attend
    key = lambda n, nk, **kw: ragged_block_attend.AttendPlanKey(
        n, nk, 128, 128, jnp.bfloat16, jnp.bfloat16, **kw)
    for n, nk in ((28, 4), (32, 4), (32, 2), (40, 8)):
      for window in (0, 2048):
        assert key(n, nk, window=window, lowering="pallas").clear
        assert not key(n, nk, window=window, lowering="xla").clear
    assert not key(16, 16, lowering="pallas").clear
    diff = diff_attend.DiffPlanKey(40, 20, 64, 128, jnp.bfloat16, jnp.bfloat16,
                                   window=512, lowering="pallas")
    assert diff.kernel and diff.lanes == 8 and not diff.clear


class TestGroupedSpans:
  """A decode row's program of `_GroupedAttendKernel` walks a span of up to
  G = `_GROUPED_SPAN` of its pages (a chunk's and a tree row's keep a page):
  the output is BITWISE the same kernel's at G forced to 1, a program a page,
  and the twin's within rounding; a table entry past a row's last page never
  reaches it, wherever in a span it lies."""

  G = ragged_block_attend._GROUPED_SPAN
  PAGE, T, T_PAGES = 16, 64, 12
  PARENTS = [-1, 0, 0, 2, -1, 4, 4, 6, -1, 8, 9, 9]

  @classmethod
  def _Packs(cls):
    g, page = cls.G, cls.PAGE
    deep = lambda pages, slots=7: (pages - 1) * page + slots - 1  # position
    return {
        # decode rows of 1, G - 1, G, G + 1 and 2G + 1 pages, the last page
        # part full
        "decode_rows": ([1] * 5, [deep(n) for n in (1, g - 1, g, g + 1,
                                                   2 * g + 1)], 0, None),
        # the same rows, the last page ONE slot full
        "last_page_one_slot": ([1] * 5, [deep(n, 1) for n in (
            1, g - 1, g, g + 1, 2 * g + 1)], 0, None),
        # and the last page full to its last slot
        "last_page_full": ([1] * 5, [deep(n, page) for n in (
            1, g - 1, g, g + 1, 2 * g + 1)], 0, None),
        # a window whose first page lies mid-stride: page0 = 3, 6 and 1 with
        # G + 1, G and 2 pages in reach
        "window_mid_stride": ([1, 1, 1, 0], [
            (3 + g) * page + 5, (6 + g) * page - 2, 2 * page + 9, 4],
                              g * page + 3, None),
        # decode rows beside a chunk that starts mid-page and a 13-node tree
        "decode_chunk_tree": ([1, 21, 1, 13, 1], [
            deep(2 * g + 1), 5 * page + 3, deep(g + 1, 1), 3 * page + 1,
            deep(2)], 0, {3: cls.PARENTS}),
        "decode_chunk_tree_window": ([1, 21, 1, 13, 1], [
            deep(2 * g + 1), 5 * page + 3, deep(g + 1, 1), 3 * page + 1,
            deep(2)], 3 * page + 5, {3: cls.PARENTS}),
    }

  def _Case(self, name, heads, seed=7, poison=True):
    lens, q_pos, window, parents = self._Packs()[name]
    n, nk, h, tile = heads
    page, t_pages, b = self.PAGE, self.T_PAGES, len(lens)
    rows = ragged.BuildRaggedRows(np.array(lens), np.array(q_pos), self.T, 32,
                                  row_parents=parents)
    rows = ragged.RaggedRows(*(jnp.asarray(m) for m in rows))
    tok = ragged.BuildTokenView(rows, b, t_pages, page)
    rng = np.random.RandomState(seed)
    np_total = b * t_pages + 1
    tables = rng.permutation(np_total - 1).reshape(b, t_pages).astype(np.int32)
    # every page no query may see is poison
    seen = np.zeros((np_total,), bool)
    for r, (n_r, pos) in enumerate(zip(lens, q_pos)):
      if n_r:
        lo = max(pos + 1 - window, 0) // page if window else 0
        seen[tables[r, lo:(pos + n_r - 1) // page + 1]] = True
    pools = [np.where(seen[:, None, None, None] | (not poison),
                      rng.randn(np_total, page, nk // tile, h * tile), np.nan)
             for _ in range(2)]
    q = jnp.asarray(rng.randn(self.T, n, h) * h ** -0.5, jnp.float32)
    tree_kw = dict(q_start=tok.q_start, anc_lo=rows.anc_lo,
                   anc_hi=rows.anc_hi) if parents else {}

    def _Call(lowering, tables=tables, pools=pools, span=None):
      with pytest.MonkeyPatch.context() as mp:
        if span is not None:
          mp.setattr(ragged_block_attend, "_GROUPED_SPAN", span)
        kp, vp = (jnp.asarray(x, jnp.float32) for x in pools)
        return np.asarray(ragged_block_attend.RaggedAttend(
            q, kp, vp, jnp.asarray(tables), tok.row, tok.q_end,
            page_size=page, window=window, lowering=lowering, interpret=True,
            **tree_kw))

    key = ragged_block_attend.AttendPlanKey(
        n, nk, h, page, q.dtype, jnp.float32, window=window,
        tree=bool(parents), lowering="pallas")
    blocks = ragged_block_attend.BuildAttendPlan(
        key, tok.row, tok.q_end, *tree_kw.values(), b=b, t_pages=t_pages)
    return key, blocks, rows, tok, tables, pools, seen, _Call

  HEADS = {"group4": (8, 2, 128, 1), "group7_of_4": (28, 4, 128, 1),
           "two_heads_a_row": (8, 2, 64, 2), "four_rows_of_two": (32, 8, 64, 2)}

  @pytest.mark.parametrize("heads", list(HEADS))
  @pytest.mark.parametrize("name", [
      "decode_rows", "last_page_one_slot", "last_page_full",
      "window_mid_stride", "decode_chunk_tree", "decode_chunk_tree_window"])
  def test_a_span_a_program_is_bitwise_a_page_a_program(self, name, heads):
    key, blocks, rows, tok, _, pools, _, call = self._Case(
        name, self.HEADS[heads])
    g = self.G
    assert key.span == g > 1 and key.clear
    assert ragged_block_attend.TileHeads(*self.HEADS[heads][:3]) == (
        self.HEADS[heads][3])
    n, page0, last = (np.asarray(x) for x in (blocks.n, blocks.page0,
                                              blocks.last))
    pairs = int(blocks.pairs)
    blk, page = (np.asarray(x)[:pairs] for x in (blocks.blk, blocks.page))
    rung = ragged_block_attend.ClearRung(
        ragged_block_attend.BlockRungs(key.bq, key.lanes))
    for i in np.flatnonzero(n > 0):
      mine = page[blk == i]
      if n[i] <= rung:       # a decode row: spans from page0, G pages apart
        assert mine.tolist() == list(range(page0[i], last[i] + 1, g))
      else:                  # a chunk's, a tree row's block: a page an entry
        assert mine.tolist() == list(range(page0[i], last[i] + 1))
    if not key.tree:
      assert pairs == ragged_block_attend.Programs(
          key, rows.row_q_pos, rows.row_len, self.T_PAGES)
      assert pairs < ragged_block_attend.LivePairs(
          key, rows.row_q_pos, rows.row_len, self.T_PAGES)
    if name == "window_mid_stride":
      assert sorted(page0[n > 0] % g)[-1] > 0 and np.any(
          (last - page0)[n > 0] >= g)
    out = call("pallas")
    assert np.all(np.isfinite(out))
    np.testing.assert_array_equal(out, call("pallas", span=1))
    np.testing.assert_array_equal(out[np.asarray(tok.q_end) == 0], 0.0)
    # the twin masks what it gathers, and a masked NaN is still one
    clean = [np.nan_to_num(x) for x in pools]
    np.testing.assert_allclose(out, call("xla", pools=clean), rtol=0,
                               atol=2e-5)

  @pytest.mark.parametrize("heads", ["group4", "two_heads_a_row"])
  @pytest.mark.parametrize("name", ["decode_rows", "window_mid_stride",
                                    "decode_chunk_tree"])
  def test_a_stale_entry_inside_a_span_never_leaks(self, name, heads):
    """`test_stale_table_entries_never_leak`'s page reuse, moved into a span:
    every table entry past a row's last live page (the slots of its last span
    that lie past `last`, and all behind them) and behind its window names
    another row's LIVE page or a page of NaN; the output does not move by a
    bit, at G and at G forced to 1."""
    key, blocks, _, tok, tables, pools, seen, call = self._Case(
        name, self.HEADS[heads])
    lens, q_pos, window, _ = self._Packs()[name]
    live_pages = np.flatnonzero(seen)
    dead_pages = np.flatnonzero(~seen)
    hostile = tables.copy()
    rng = np.random.RandomState(11)
    spans_with_dead_slots = 0
    for r, (n_r, pos) in enumerate(zip(lens, q_pos)):
      last = (pos + max(n_r, 1) - 1) // self.PAGE if n_r else -1
      lo = max(pos + 1 - window, 0) // self.PAGE if window and n_r else 0
      for p in range(self.T_PAGES):
        if p > last or p < lo:
          foreign = [x for x in live_pages if x not in tables[r, lo:last + 1]]
          hostile[r, p] = rng.choice(foreign if (p + r) % 2 else dead_pages)
      if n_r == 1 and (last - lo + 1) % self.G:
        spans_with_dead_slots += 1
    assert spans_with_dead_slots >= 2 and np.any(hostile != tables)
    out = call("pallas")
    assert np.all(np.isfinite(out))
    np.testing.assert_array_equal(out, call("pallas", tables=hostile))
    np.testing.assert_array_equal(out, call("pallas", tables=hostile, span=1))


@pytest.mark.parametrize("shape", ["smallthinker", "trinity", "lfm2"])
def test_the_kernel_probe_holds_the_clear_body_to_the_masked_one(shape,
                                                                 capsys):
  """tools/kernel_probe.py --case grouped_attend at the CPU's rehearsal
  sizes: a full and a window layer, a decode-only step and one with a chunk
  whose blocks hold clear pages; `clear` is bitwise `masked`, and so is
  `span1`, the kernel at a program a page (`lfm2`: heads of 64, two KV heads a
  row of the pool)."""
  import importlib.util
  import json
  import os
  spec = importlib.util.spec_from_file_location("kernel_probe", os.path.join(
      os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools",
      "kernel_probe.py"))
  kernel_probe = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(kernel_probe)
  assert kernel_probe.main(["--case", "grouped_attend", "--tiny", "--calls",
                            "1", "--shapes", shape]) == 0
  lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
           if l.startswith("{")]
  n, n_kv, windows = kernel_probe.ATTEND_SHAPES[shape][:3]
  assert [(l["window"], l["step"], l["variant"]) for l in lines] == [
      (w, s, v) for w in windows for s in ("decode", "chunk@0k")
      for v in ("masked", "clear", "span1")]
  assert all(l["bitwise_the_first"] for l in lines
             if l["variant"] != "masked")
  # `span1` runs a program a page; the kernel as built fewer where a decode
  # row holds more pages than one
  for l in lines:
    pages = l["decode_pairs"] + l["chunk_pairs"]
    assert l["programs"] == pages if l["variant"] == "span1" else (
        l["programs"] < pages)
  assert all(l["tiny"] and l["device"]["platform"] == "cpu"
             and (l["heads"], l["kv_heads"]) == (n, n_kv) for l in lines)
  for l in lines:
    chunk = l["step"] != "decode"
    assert (l["chunk_pairs"] > 0) == chunk and l["decode_pairs"] > 0
    assert (l["clear_pairs"] > 0) == (chunk and l["variant"] != "masked")
    assert ("us_a_chunk_pair" in l) == chunk
