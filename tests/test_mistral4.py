"""Mistral-Small-4's mechanisms at a size the CPU holds, against the plain
reference (benchmarks/references/mistral4.py: the EXPANDED form in f32) and
against numpy: latent attention's two forms, yarn frequencies, interleaved
rotation and the query's position scale, the latent attend op's two
lowerings, the expert layer that holds a share of the experts its router
scores, the latent pool in the engine (census, layout, copy, spill,
restore, prefix cache), the refusals, and the tiny registered sibling served
by ServingLoop in chunks and decode steps through poisoned pages."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import mistral4 as ref
from lingvo_tpu import model_registry
from lingvo_tpu.core import mla as mla_lib
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.ops import latent_attend
from lingvo_tpu.ops import ragged_block_attend as rba
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import spec_decode
from lingvo_tpu.serving import state_layout

import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)

# the served f32 model (absorbed form, pages, chunks) against the f32
# reference (expanded form, one pass): both sum in f32 in another order, and
# the largest difference over every case below is 3e-5; the same weights
# rounded to bf16 read 5e-3 and more (test_bf16_weights_fail_the_tolerance)
_LOGIT_TOL = 2e-4
# MistralSmall4Tiny's rotary window: a(t) steps at 32, 64, 96, and yarn's
# ramp lies inside the rotary part's four pairs
_ORIGINAL = 32


def _Task(name="MistralSmall4Tiny", **task_params):
  mp = model_registry.GetParams("lm.mistral4." + name, "Train")
  tp = mp.task
  tp.input = mp.input
  for key, value in task_params.items():
    tp.SetPath(key, value)
  task = tp.Instantiate()
  task.FinalizePaths()
  return task


def _Share(first, held):
  return {"expert_ffn_tpl.first_expert": first,
          "expert_ffn_tpl.num_experts_held": held}


def _Seeded(task, first=0, key=7, **weights):
  theta = task.InstantiateVariables(jax.random.PRNGKey(key))
  kw = dict(attention_out_scale=4.0, router_scale=3.0, first_expert=first)
  kw.update(weights)
  return ref.SeededWeights(theta, **kw)


@pytest.fixture(scope="module")
def tiny():
  """{name: (task, theta)}: the whole layer, and the share the rehearsal
  runs (experts 2-3 of 8)."""
  out = {}
  for name, (first, held) in {"whole": (0, 0), "share": (2, 2)}.items():
    task = _Task(**_Share(first, held))
    out[name] = task, _Seeded(task, first), first
  return out


def _Restate(first):
  """The reference reads what no shape says from what SeededWeights was
  told last: a test of another share says so again."""
  ref._STATED["first_expert"] = first


def _ReferenceLogits(theta, seq, at, first=0, width=160):
  _Restate(first)
  ids = np.zeros((1, width), np.int32)
  ids[0, :len(seq)] = seq
  return np.asarray(jax.jit(lambda th, i, a: ref.LogitsAt(th, i, a, 0.0))(
      theta, jnp.asarray(ids), jnp.asarray([at], jnp.int32)))[0]


# -- rotary: yarn's frequencies, interleaved pairs, a(t) -----------------------


def _HfYarnInvFreq(dim, base, factor, original, beta_fast, beta_slow):
  """transformers' `_compute_yarn_parameters`, written out in numpy."""
  pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
  extra, inter = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

  def _Dim(rot):
    return dim * np.log(original / (rot * 2 * np.pi)) / (2 * np.log(base))

  low = max(np.floor(_Dim(beta_fast)), 0)
  high = min(np.ceil(_Dim(beta_slow)), dim - 1)
  if low == high:
    high += 0.001
  ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
  return inter * ramp + extra * (1 - ramp)


@pytest.mark.parametrize("dim,base,factor,original,fast,slow", [
    (64, 1e4, 128.0, 8192, 32.0, 1.0),      # the published row's
    (8, 1e4, 8.0, 32, 4.0, 1.0),            # the tiny sibling's
    (16, 5e5, 4.0, 128, 32.0, 1.0),
    (64, 1e4, 1.0, 8192, 32.0, 1.0),        # no yarn
])
def test_yarn_frequencies_are_the_published_codes(dim, base, factor, original,
                                                  fast, slow):
  want = _HfYarnInvFreq(dim, base, factor, original, fast, slow)
  got = mla_lib.YarnInvFreq(dim, base, factor, original, fast, slow)
  np.testing.assert_allclose(got, want, rtol=1e-6)
  if factor > 1:
    # the fastest pair keeps its frequency, the slowest has it divided
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(base ** (-(dim - 2) / dim) / factor)
    ref._ARCH.update(rope_theta=base, rope_factor=factor,
                     rope_original=original, beta_fast=fast, beta_slow=slow)
    np.testing.assert_allclose(ref._InvFreq(dim), want, rtol=1e-6)


def test_rotation_is_by_interleaved_pairs():
  """Pair (2j, 2j + 1) turns by pos * f_j; a rotation by halves (the tree's
  other rotary layer) is another function of the same vector."""
  rng = np.random.RandomState(0)
  x = rng.randn(5, 3, 8).astype(np.float32)
  pos = np.array([0, 1, 7, 40, 1000])
  freq = mla_lib.YarnInvFreq(8, 1e4, 8.0, 32, 4.0, 1.0)
  got = np.asarray(mla_lib.RotateInterleaved(jnp.asarray(x), pos[:, None],
                                             freq))
  want = np.zeros_like(x)
  for t in range(5):
    for j in range(4):
      c, s = np.cos(pos[t] * freq[j]), np.sin(pos[t] * freq[j])
      want[t, :, 2 * j] = x[t, :, 2 * j] * c - x[t, :, 2 * j + 1] * s
      want[t, :, 2 * j + 1] = x[t, :, 2 * j + 1] * c + x[t, :, 2 * j] * s
  np.testing.assert_allclose(got, want, atol=1e-5)
  np.testing.assert_array_equal(got[0], x[0])          # position 0
  halves = np.concatenate([x[..., :4] * np.cos(pos[:, None, None] * freq)
                           - x[..., 4:] * np.sin(pos[:, None, None] * freq),
                           x[..., 4:] * np.cos(pos[:, None, None] * freq)
                           + x[..., :4] * np.sin(pos[:, None, None] * freq)],
                          -1)
  assert np.abs(halves[2] - got[2]).max() > 0.1
  # a score is a function of the distance alone
  q, k = rng.randn(8).astype(np.float32), rng.randn(8).astype(np.float32)
  dots = [float(mla_lib.RotateInterleaved(jnp.asarray(q), a, freq)
                @ mla_lib.RotateInterleaved(jnp.asarray(k), b, freq))
          for a, b in ((9, 2), (107, 100), (47, 40))]
  assert dots[0] == pytest.approx(dots[1], abs=1e-4)
  assert dots[0] == pytest.approx(dots[2], abs=1e-4)


def _Mixer(**kw):
  p = mla_lib.MultiHeadLatentAttention.Params().Set(
      name="mla", input_dim=48, num_heads=4, q_lora_rank=24, kv_lora_rank=16,
      qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=10,
      rope_factor=8.0, rope_original_max_position=_ORIGINAL,
      rope_beta_fast=4.0, llama_4_scaling_beta=0.1, **kw)
  layer = p.Instantiate()
  layer.FinalizePaths()
  return layer, layer.InstantiateVariables(jax.random.PRNGKey(2))


def test_the_scale_carries_yarns_m_squared_and_the_positions_a_of_t():
  layer, _ = _Mixer()
  m = 0.1 * np.log(8.0) + 1.0
  scale = 20 ** -0.5 * m * m
  got = np.asarray(layer._QueryScale(jnp.asarray([0, 31, 32, 63, 64, 200])))
  want = scale * (1 + 0.1 * np.log1p(np.array([0, 0, 1, 1, 2, 6.0])))
  np.testing.assert_allclose(got, want, rtol=1e-6)
  plain, _ = _Mixer(rope_mscale_all_dim=0.0)
  assert float(plain._QueryScale(jnp.asarray([5]))[0]) == pytest.approx(
      20 ** -0.5)
  none = mla_lib.MultiHeadLatentAttention.Params().Set(
      name="mla", input_dim=48, num_heads=4, q_lora_rank=24, kv_lora_rank=16,
      qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=10).Instantiate()
  np.testing.assert_allclose(np.asarray(none._QueryScale(jnp.arange(3) * 9000)),
                             20 ** -0.5)


# -- the attend op: two lowerings against numpy --------------------------------


def _NumpyLatentAttend(q, pool, tables, row_of, q_end, page, v):
  t, n, _ = q.shape
  out = np.zeros((t, n, v), np.float32)
  for i in range(t):
    e = int(q_end[i])
    if e == 0:
      continue
    rows = np.stack([pool[tables[row_of[i], s // page], s % page]
                     for s in range(e)])
    for h in range(n):
      sc = rows @ q[i, h]
      p = np.exp(sc - sc.max())
      out[i, h] = (p / p.sum()) @ rows[:, :v]
  return out


# (tokens of a row, its first token's q_end) and the padding tokens after it:
# a decode row, a two-token row, a row of exactly one block and a chunk that
# spans blocks with a ragged last one
_EVERY_RUNG = (((1, 51), 1), ((2, 30), 2), ((8, 17), 0), ((21, 11), 5))


@pytest.mark.parametrize("lowering", ["xla", "pallas"])
@pytest.mark.parametrize("heads,row,value,pages", [
    (5, 40, 32, "f32"), (32, 48, 32, "f32"), (4, 24, 16, "f32"),
    (16, 40, 32, "bf16")])
def test_latent_attend_is_numpys(lowering, heads, row, value, pages,
                                 monkeypatch):
  """Rows of several lengths and padding in one pack; the pool's pages that
  no table names hold NaN and so do the padding tokens' queries. A token's
  value is the first `value` columns of the row its score read whole."""
  monkeypatch.setattr(latent_attend, "_BQ", 8 * latent_attend.Lanes(heads))
  rng = np.random.RandomState(heads + row)
  page, rows = 8, len(_EVERY_RUNG)
  t_pages = -(-max(n + e for (n, e), _ in _EVERY_RUNG) // page)
  pool_pages = rows * t_pages + 3
  pool = rng.randn(pool_pages, page, row).astype(np.float32)
  tol = 3e-5
  if pages == "bf16":
    pool = np.array(jnp.asarray(pool, jnp.bfloat16).astype(jnp.float32))
    tol = 3e-2
  tables = rng.permutation(pool_pages - 3)[:rows * t_pages].reshape(
      rows, t_pages).astype(np.int32)
  row_of, q_end = [], []
  for r, ((n, first_end), pad) in enumerate(_EVERY_RUNG):
    row_of += [r] * n + [0] * pad
    q_end += list(range(first_end, first_end + n)) + [0] * pad
  row_of, q_end = np.array(row_of, np.int32), np.array(q_end, np.int32)
  q = rng.randn(len(row_of), heads, row).astype(np.float32) / np.sqrt(row)
  if pages == "bf16":
    q = np.array(jnp.asarray(q, jnp.bfloat16).astype(jnp.float32))
  want = _NumpyLatentAttend(q, pool, tables, row_of, q_end, page, value)
  q[q_end == 0] = np.nan
  pool[-3:] = np.nan
  dtype = jnp.bfloat16 if pages == "bf16" else jnp.float32
  got = np.asarray(latent_attend.LatentAttend(
      jnp.asarray(q, dtype), jnp.asarray(pool, dtype), jnp.asarray(tables),
      jnp.asarray(row_of), jnp.asarray(q_end), page_size=page,
      value_dim=value, lowering=lowering).astype(jnp.float32))
  np.testing.assert_allclose(got, want, atol=tol)
  assert (got[q_end == 0] == 0).all()


def test_the_latent_kernel_runs_the_steps_plan_and_its_rungs():
  """The descriptors are ops/ragged_block_attend's, under a key of this
  op's: a token lays its heads padded to 16 on the packed axis, a block
  holds whole tokens, and a decode row runs the lower rung."""
  key = latent_attend.PlanKey(32, 128, lowering="pallas")
  assert key == rba.PlanKey(128, 0, 1024, 32, True, True, clear=True)
  assert latent_attend.PlanKey(4, 8, lowering="pallas").lanes == 16
  assert latent_attend.QueryBlock(4) == 1024
  assert latent_attend.QueryBlock(48) == 21 * 48
  assert not latent_attend.PlanKey(32, 128, lowering="xla").kernel
  assert rba.BlockRungs(key.bq, key.lanes) == (32, 1024)
  assert rba.LivePairs(key, [5000, 300], [1, 40], 196) == 40 + 2 * 3
  # the chunk's two blocks start at tokens 300 and 332: two whole pages lie
  # under either's first horizon; the decode row's rung runs one body
  assert rba.ClearPairs(key, [5000, 300], [1, 40], 196) == 2 * 2
  assert rba.ClearRung(rba.BlockRungs(key.bq, key.lanes)) == 32
  assert latent_attend.SupportedOnTpu(128, 256)
  assert not latent_attend.SupportedOnTpu(8, 16)


# a pack of every kind of row the clear pages must leave alone (heads 4:
# 16 lanes; Bq 8 tokens; pages of 8): (tokens, first q_end), padding after
_CLEAR_PACK = (((16, 41), 0),    # a full chunk, two blocks, five clear pages
               ((11, 30), 3),    # a partial last block beside padding tokens
               ((1, 51), 0),     # a decode row
               ((1, 6), 1),      # a decode row shorter than a page
               ((3, 5), 2),      # a row shorter than a page, in the wide rung
               ((12, 27), 1))    # (the tree row, where the case asks for one)


def _ClearCase(tree, dtype=jnp.float32, page=8, seed=0):
  """(args of LatentAttend, its keywords, q_end, the plan's `clear` by
  numpy) of `_CLEAR_PACK` with its positions scaled to pages of `page`, and
  with `tree` the last row's tokens as a root and an 11-node tree."""
  rng = np.random.RandomState(seed)
  heads, scale = 4, page // 8
  row, value = (24, 16) if page == 8 else (page + 8, page)
  t_pages = -(-max(n + e * scale for (n, e), _ in _CLEAR_PACK) // page)
  rows = len(_CLEAR_PACK)
  pool = rng.randn(rows * t_pages + 2, page, row).astype(np.float32)
  pool[-2:] = np.nan                        # pages no table names
  tables = rng.permutation(rows * t_pages).reshape(rows, t_pages)
  row_of, q_end, q_start, lo, hi, clear = [], [], [], [], [], []
  for r, ((n, first_end), pad) in enumerate(_CLEAR_PACK):
    first_end *= scale
    row_of += [r] * n + [0] * pad
    q_end += list(range(first_end, first_end + n)) + [0] * pad
    q_start += [first_end - 1] * n + [0] * pad
    lo += [-1] * (n + pad)
    hi += [-1] * (n + pad)
    # a block is 8 tokens; a chain's narrowest reach is its first token's
    # horizon, the tree's the slot its window starts at, counting it
    clear += [(first_end if tree and r == rows - 1 else first_end + j)
              // page for j in range(0, n, 8)]
  if tree:
    n, pad = _CLEAR_PACK[-1][0][0], _CLEAR_PACK[-1][1]
    at = len(q_end) - n - pad
    lo[at:at + n], hi[at:at + n] = ragged_lib.TreeAncestorMasks(
        [-1, 0, 0, 2, -1, 4, 4, 6, -1, 8, 9])
  q = rng.randn(len(row_of), heads, row).astype(np.float32) / np.sqrt(row)
  q[np.array(q_end) == 0] = np.nan          # a padding token's query
  i32 = lambda x: jnp.asarray(np.array(x, np.int32))
  args = (jnp.asarray(q, dtype), jnp.asarray(pool, dtype), i32(tables),
          i32(row_of), i32(q_end))
  kw = dict(page_size=page, value_dim=value, q_start=i32(q_start),
            anc_lo=i32(lo), anc_hi=i32(hi))
  return args, kw, np.array(q_end), clear


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("page", [8, 128])
@pytest.mark.parametrize("tree", [False, True], ids=["chains", "a_tree_row"])
def test_clear_pages_are_bitwise_the_masked_pages(tree, page, dtype,
                                                  monkeypatch):
  """The kernel with the plan's `clear` against the same kernel with `clear`
  forced to 0 (every page masked: the kernel as it was), bit for bit, and
  against the twin within rounding; padding tokens an exact zero and the
  tokens after them what they were. Pages of 128 run the clear body the cell
  runs: the statistics lane-replicated, the rows as two halves."""
  monkeypatch.setattr(latent_attend, "_BQ", 8 * latent_attend.Lanes(4))
  args, kw, q_end, want = _ClearCase(tree, dtype, page)
  key = latent_attend.PlanKey(4, page, lowering="pallas")
  assert key.clear and (key.bq, key.lanes) == (128, 16)
  blocks = rba.BuildAttendPlan(key, args[3], args[4], kw["q_start"],
                               kw["anc_lo"], kw["anc_hi"], b=len(_CLEAR_PACK),
                               t_pages=args[2].shape[1])
  clear = np.asarray(blocks.clear)[:int(np.sum(np.asarray(blocks.n) > 0))]
  assert clear.tolist() == want and (want[0], want[2], want[5]) == (5, 3, 0)
  call = lambda plan: np.asarray(latent_attend.LatentAttend(
      *args, **kw, lowering="pallas", interpret=True,
      plan={key: plan}).astype(jnp.float32))
  got = call(blocks)
  masked = call(blocks._replace(clear=jnp.zeros_like(blocks.clear)))
  np.testing.assert_array_equal(got, masked)
  # and the comparison sees a page that is run unmasked and is not clear
  wrong = call(blocks._replace(clear=blocks.clear + 1))
  assert not np.array_equal(wrong[q_end > 0], got[q_end > 0])
  assert np.all(np.isfinite(got))
  assert (got[q_end == 0] == 0).all()
  assert (got[q_end > 0] != 0).any(axis=(1, 2)).all()
  clean = (args[0], jnp.nan_to_num(args[1])) + args[2:]
  twin = np.asarray(latent_attend.LatentAttend(
      *clean, **kw, lowering="xla").astype(jnp.float32))
  np.testing.assert_allclose(got[q_end > 0], twin[q_end > 0],
                             atol=3e-5 if dtype == jnp.float32 else 3e-2)


def test_a_tree_mask_without_the_root_bit_clears_no_page(monkeypatch):
  """`clear` trusts no convention: a query whose mask leaves bit 0 unset
  (no tree the engine builds) keeps every page of its block masked."""
  monkeypatch.setattr(latent_attend, "_BQ", 8 * latent_attend.Lanes(4))
  args, kw, _, want = _ClearCase(True)
  kw["anc_lo"] = kw["anc_lo"].at[-3].set(2)
  key = latent_attend.PlanKey(4, 8, lowering="pallas")
  blocks = rba.BuildAttendPlan(key, args[3], args[4], kw["q_start"],
                               kw["anc_lo"], kw["anc_hi"], b=len(_CLEAR_PACK),
                               t_pages=args[2].shape[1])
  assert want[7:] == [27 // 8, 27 // 8]
  assert np.asarray(blocks.clear)[7:9].tolist() == [27 // 8, 0]


def test_the_latent_kernel_compiles_for_a_v5e_at_the_cells_shapes():
  """What Mosaic must accept, without a chip (tests/test_chip_compile.py's
  way): 1,088 packed tokens of 32 heads over rows stored at 384, pages of
  128, 196 pages a row, six layers' pool as one; a row of 320 it refuses
  (a copy moves whole 128-lane tiles), which is why the row is stored at
  384."""
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache
  from jax.sharding import SingleDeviceSharding
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # noqa: BLE001 - no TPU compiler here
    pytest.skip(f"cannot describe a v5e topology: {e}")
  one = SingleDeviceSharding(topo.devices[0])
  sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)
  t, n, page, t_pages, pool_pages, b = 1088, 32, 128, 196, 6 * 4609, 64

  def _Lower(row):
    fn = lambda q, pool, tables, tok, end: latent_attend.LatentAttend(
        q, pool, tables, tok, end, q_start=tok, anc_lo=tok, anc_hi=tok,
        page_size=page, value_dim=256, lowering="pallas", interpret=False)
    return jax.jit(fn).lower(
        sds((t, n, row), jnp.bfloat16), sds((pool_pages, page, row),
                                            jnp.bfloat16),
        sds((b, t_pages), jnp.int32), sds((t,), jnp.int32),
        sds((t,), jnp.int32))

  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  try:
    assert "tpu_custom_call" in _Lower(384).compile().as_text()
    with pytest.raises(Exception, match="aligned to tiling"):
      _Lower(320).compile()
  finally:
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
  assert mla_lib.StoredRow(256, 64) == 384 and mla_lib.StoredRow(16, 8) == 24


# -- the mixer: absorbed against expanded --------------------------------------


@pytest.mark.parametrize("layered", [False, True])
def test_absorbed_through_pages_is_expanded_in_one_pass(layered):
  """RaggedMix (the absorbed form over the latent pool, a chunk a call) and
  FProp (the expanded form, the whole sequence at once) on one mixer: equal
  to rounding at every position, past two periods of a(t); what the pool
  holds a token is [RMSNorm(c_kv) | rotated k_r]; a stacked pool is read and
  written as one, this layer's pages at its base."""
  from lingvo_tpu.core import ragged as ragged_lib
  layer, theta = _Mixer()
  t, page, t_pages = 80, 8, 12
  x = jnp.asarray(np.random.RandomState(3).randn(1, t, 48), jnp.float32)
  want, _ = layer.FProp(theta, x, causal=True)
  states = layer.InitPagedStates(theta, 14, page)
  assert states.latent.shape == (14, page, 24)
  which = None
  if layered:
    states = jax.tree_util.tree_map(
        lambda a: jnp.full((3,) + a.shape, jnp.nan, a.dtype), states)
    states.latent = states.latent.at[1].set(0.0)
    which = 1
  tables = jnp.asarray(np.random.RandomState(4).permutation(13)[:t_pages][
      None].astype(np.int32))
  got = []
  for start in range(0, t, 20):
    rows = ragged_lib.RaggedRows(*(jnp.asarray(a) for a in
                                   ragged_lib.BuildRaggedRows(
                                       [20], [start], 24, 20)))
    chunk = jnp.pad(x[:, start:start + 20], ((0, 0), (0, 4), (0, 0)))
    o, states = layer.RaggedStep(theta, chunk, states, tables, rows,
                                 layer=which)
    got.append(o[:, :20])
  np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                             np.asarray(want), atol=2e-5)
  _, _, c_kv, k_r = layer._Project(theta, x, jnp.arange(t)[None])
  pool = np.asarray(states.latent[1] if layered else states.latent)
  for s in (0, 7, 8, 79):
    held = pool[int(tables[0, s // page]), s % page]
    np.testing.assert_allclose(held, np.concatenate(
        [np.asarray(c_kv[0, s]), np.asarray(k_r[0, s])]), atol=1e-6)
  if layered:
    assert np.isnan(np.asarray(states.latent[0])).all()
    assert np.isnan(np.asarray(states.latent[2])).all()


def test_fprop_masks_paddings_and_segments():
  layer, theta = _Mixer()
  x = jnp.asarray(np.random.RandomState(5).randn(2, 12, 48), jnp.float32)
  base, _ = layer.FProp(theta, x, causal=True)
  pad = jnp.zeros((2, 12)).at[:, 9:].set(1.0)
  padded, _ = layer.FProp(theta, x.at[:, 9:].set(99.0), paddings=pad,
                          causal=True)
  np.testing.assert_allclose(np.asarray(padded[:, :9]),
                             np.asarray(base[:, :9]), atol=1e-5)
  full, _ = layer.FProp(theta, x)
  assert np.abs(np.asarray(full - base)).max() > 1e-3


# -- the model: forward, and served through the pages --------------------------


@pytest.mark.parametrize("name", ["whole", "share"])
def test_whole_model_forward_is_the_references(tiny, name):
  """TransformerLm's own forward (the expanded form, every position at once)
  against the reference's logits at positions on both sides of a(t)'s steps."""
  from lingvo_tpu.core.nested_map import NestedMap
  task, theta, first = tiny[name]
  seq = np.random.RandomState(1).randint(1, 128, 100).astype(np.int32)
  logits = np.asarray(jax.jit(lambda th, ids: task.ComputePredictions(
      th, NestedMap(ids=ids, paddings=jnp.zeros(ids.shape))).logits)(
          theta, jnp.asarray(seq)[None]))[0]
  for at in (0, 31, 32, 70, 99):
    np.testing.assert_allclose(logits[at],
                               _ReferenceLogits(theta, seq, at, first),
                               atol=_LOGIT_TOL, err_msg=f"position {at}")


class _Probe:
  """Every step through the task's ragged step with its logits kept:
  {(slot, position): logits [V]} of every valid token."""

  def __init__(self, engine, task):
    self.engine, self.seen = engine, {}
    self._fn = jax.jit(lambda th, st, ids, rows, tables: task.RaggedStep(
        th, ids[None], st, tables, rows))
    self._inner = engine._compile_log.Call
    engine._compile_log.Call = self._Call

  def _Call(self, name, fn, *args):
    if name != "ragged":
      return self._inner(name, fn, *args)
    theta, states, tok_ids, rows, tables = args[:5]
    logits, new_states = self._fn(theta, states, tok_ids, rows, tables)
    logits = np.asarray(logits[0])
    for col in np.flatnonzero(np.asarray(rows.valid)):
      key = int(np.asarray(rows.row_of)[col]), int(np.asarray(rows.pos)[col])
      self.seen[key] = logits[col]
    return jnp.asarray(logits.argmax(-1), jnp.int32), new_states


def _Engine(task, theta, slots, **kw):
  kw.setdefault("num_pages", 48)
  return engine_lib.ServingLoop(task, theta, page_size=8, max_batch=slots,
                                max_seq_len=160, prefill_token_budget=16,
                                **kw)


def _PoisonPagesNoRowHolds(eng):
  """NaN into every page of every layer's pool that no live row holds (the
  trash page too), and a huge number into the slots of held pages past a
  row's cursor: what no query may read."""
  page = eng.page_size
  held = {}
  for seq in eng.sched.slots:
    if seq is not None:
      for i, p in enumerate(eng.alloc.PagesOf(seq.id)):
        held[int(p)] = max(0, min(page, seq.pos - i * page))
  pool = eng._states.body.self_atten.latent
  free = jnp.asarray([p for p in range(pool.shape[1]) if p not in held],
                     jnp.int32)
  pool = pool.at[:, free].set(jnp.nan)
  for p, live in held.items():
    pool = pool.at[:, p, live:].set(3e4)
  eng._states.body.self_atten.latent = pool


def _Serve(task, theta, prompts, new_tokens, poison=False, between=None,
           **kw):
  eng = _Engine(task, theta, len(prompts), **kw)
  probe = _Probe(eng, task)
  handles = [eng.Submit(p, new_tokens) for p in prompts]
  for step in range(400):
    if all(h.done for h in handles):
      break
    eng.StepOnce()
    if poison:
      _PoisonPagesNoRowHolds(eng)
    if between is not None:
      between(eng, step)
  assert all(h.done for h in handles)
  return eng, probe.seen, [h.Result() for h in handles]


_PROMPTS = {"past_two_periods": [90], "shorter_than_a_chunk": [10],
            "three_rows_in_one_step": [90, 10, 50]}


@pytest.mark.parametrize("name", ["whole", "share"])
@pytest.mark.parametrize("case", list(_PROMPTS))
def test_chunked_prefill_and_decode_match_the_reference(tiny, case, name):
  """Prefill in chunks of 16 and 8 decode steps through the latent pages,
  with every page no row holds poisoned after every step: the step's logits
  at the end of the prompt and at the last token fed back equal the
  reference's full forward (expanded form) there, past a(t)'s second step."""
  task, theta, first = tiny[name]
  rng = np.random.RandomState(5)
  prompts = [rng.randint(1, 128, n).astype(np.int32) for n in _PROMPTS[case]]
  eng, seen, outs = _Serve(task, theta, prompts, 8, poison=True)
  for slot, (prompt, out) in enumerate(zip(prompts, outs)):
    seq = np.concatenate([prompt, np.asarray(out, np.int32)])
    for at in (len(prompt) - 1, len(seq) - 2):
      want = _ReferenceLogits(theta, seq, at, first)
      np.testing.assert_allclose(seen[slot, at], want, atol=_LOGIT_TOL,
                                 err_msg=f"row {slot} position {at}")
      assert int(want.argmax()) == seq[at + 1]
  assert all(np.isfinite(v).all() for v in seen.values())
  assert eng.Stats()["kv_pages"]["in_use"] == 0
  assert eng.paged_path == "xla"


def test_bf16_weights_fail_the_tolerance(tiny):
  task, theta, first = tiny["share"]
  rounded = jax.tree_util.tree_map(
      lambda x: x.astype(jnp.bfloat16).astype(x.dtype), theta)
  prompt = np.random.RandomState(5).randint(1, 128, 40).astype(np.int32)
  _, seen, outs = _Serve(task, rounded, [prompt], 2)
  seq = np.concatenate([prompt, np.asarray(outs[0], np.int32)])
  diff = np.abs(seen[0, 39] - _ReferenceLogits(theta, seq, 39, first)).max()
  assert diff > 10 * _LOGIT_TOL, diff


@pytest.mark.parametrize("wrong", ["unrotated_k_r", "no_a_of_t", "no_m2",
                                   "halves", "shifted_share"])
def test_the_reference_tells_a_wrong_model_from_the_right_one(tiny, wrong):
  """What the tolerance must see, at the tiny size: each of these served
  models differs from the reference by a hundred tolerances and more at a
  position past a(t)'s first step."""
  _, theta, first = tiny["share"]
  params = {
      "unrotated_k_r": {}, "halves": {},
      "no_a_of_t": {"atten_tpl.llama_4_scaling_beta": 0.0},
      "no_m2": {"atten_tpl.rope_mscale_all_dim": 0.0},
      "shifted_share": {"expert_ffn_tpl.first_expert": 3},
  }[wrong]
  task = _Task(**{**_Share(2, 2), **params})
  patch = pytest.MonkeyPatch()
  if wrong == "unrotated_k_r":
    real = mla_lib.RotateInterleaved
    patch.setattr(mla_lib, "RotateInterleaved",
                  lambda x, pos, f: x if x.ndim == 3 else real(x, pos, f))
  elif wrong == "halves":
    def _Halves(x, pos, f):
      ang = jnp.asarray(pos, jnp.float32)[..., None] * f
      a, b = jnp.split(x, 2, -1)
      return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                              b * jnp.cos(ang) + a * jnp.sin(ang)], -1)
    patch.setattr(mla_lib, "RotateInterleaved", _Halves)
  try:
    prompt = np.random.RandomState(6).randint(1, 128, 70).astype(np.int32)
    _, seen, outs = _Serve(task, theta, [prompt], 2)
  finally:
    patch.undo()
  seq = np.concatenate([prompt, np.asarray(outs[0], np.int32)])
  diff = np.abs(seen[0, 69] - _ReferenceLogits(theta, seq, 69, first)).max()
  assert diff > 100 * _LOGIT_TOL, (wrong, diff)


# -- the shares add up ----------------------------------------------------------


def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer(tiny):
  """The guide's test that ties the share to the model: the expert layer of
  the tiny sibling as four chips would hold it (experts 0-1, 2-3, 4-5, 6-7,
  each with the router's eight columns and the shared expert) against the
  REFERENCE's uncut layer: what the four add to the stream, with the shared
  expert counted once, is the reference's expert branch; the pairs each
  counts as held and as elsewhere are all the pairs."""
  from lingvo_tpu.core import ragged as ragged_lib
  from lingvo_tpu.core.nested_map import NestedMap
  task, theta, _ = tiny["whole"]
  ff = theta.stack.body.fflayer
  th = jax.tree_util.tree_map(lambda a: a[1], ff)           # layer 1's
  tpl = task.stack.body.fflayer.p.Copy()
  t = 23
  x = jnp.asarray(np.random.RandomState(8).randn(t, 48), jnp.float32)
  ref._ARCH.clear()
  ref._ARCH.update(ref._Arch(48))
  _Restate(0)
  ref._ARCH["first_expert"] = 0
  g = ref._RmsNorm(x, th.ln.scale)
  want = ref._Experts(dict(ff), 1, g)
  shared = (jax.nn.silu(g @ th.w_shared_gate) * (g @ th.w_shared_up)
            ) @ th.w_shared_down
  rows = ragged_lib.RaggedRows(*(jnp.asarray(a) for a in
                                 ragged_lib.BuildRaggedRows([t], [0], t, t)))
  total, held, elsewhere = jnp.zeros_like(x), 0, 0
  for s in range(4):
    layer = tpl.Copy().Set(name="moe", first_expert=2 * s,
                           num_experts_held=2).Instantiate()
    layer.FinalizePaths()
    mine = NestedMap(th)
    for name in layer.StackAddressed():
      mine[name] = th[name][2 * s:2 * s + 2]
    out, states = layer.RaggedStep(mine, x[None], layer.InitPagedStates(mine),
                                   rows)
    total = total + (out[0] - x - shared)
    held += int(states.routed.sum())
    elsewhere += int(states.elsewhere)
  np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                             atol=1e-5)
  assert held == t * 2 and held + elsewhere == 4 * t * 2


def test_a_step_in_which_no_token_picks_a_held_expert(tiny):
  """Every pair elsewhere: the grouped matmuls run no row, the routed sum is
  zero and the layer adds the shared expert alone; `routed` counts nothing."""
  from lingvo_tpu.core import ragged as ragged_lib
  task, theta, _ = tiny["share"]
  layer = task.stack.body.fflayer
  th = jax.tree_util.tree_map(lambda a: a[0], theta.stack.body.fflayer)
  th.w_router = jnp.zeros_like(th.w_router)
  x = jnp.asarray(np.random.RandomState(9).randn(11, 48), jnp.float32)
  g = layer.ln.FProp(th.ln, x)
  rows = ragged_lib.RaggedRows(*(jnp.asarray(a) for a in
                                 ragged_lib.BuildRaggedRows([11], [0], 11, 11)))
  # logits 0 everywhere tie: top_k takes the lowest indices, experts 0 and 1
  out, states = layer.RaggedStep(th, x[None], layer.InitPagedStates(th), rows)
  shared = (jax.nn.silu(g @ th.w_shared_gate) * (g @ th.w_shared_up)
            ) @ th.w_shared_down
  np.testing.assert_allclose(np.asarray(out[0]), np.asarray(x + shared),
                             atol=1e-6)
  assert int(states.routed.sum()) == 0 and int(states.elsewhere) == 22


def test_the_engine_counts_held_pairs_and_pairs_elsewhere(tiny):
  task, theta, _ = tiny["share"]
  eng = _Engine(task, theta, 2)
  handle = eng.Submit(np.arange(1, 31, dtype=np.int32), 4)
  while not handle.done:
    eng.StepOnce()
  st = eng.Stats()
  tokens = st["prompt_tokens"] + st["tokens_emitted"] - 1
  assert st["moe_tokens_routed"] + st["moe_pairs_elsewhere"] == tokens * 2 * 2
  assert 0 < st["moe_tokens_routed"] < tokens * 2 * 2
  assert st["moe_expert_load_mean"] == pytest.approx(
      st["moe_tokens_routed"] / 2)
  assert 0 < st["moe_experts_active"] <= st["steps"] * 2 * 2
  records = [r for r in eng.trace.Steps() if r.counters]
  assert records[-1].counters["moe_pairs_elsewhere"] > 0
  whole = _Engine(tiny["whole"][0], tiny["whole"][1], 2)
  handle = whole.Submit(np.arange(1, 31, dtype=np.int32), 4)
  while not handle.done:
    whole.StepOnce()
  assert whole.Stats()["moe_pairs_elsewhere"] == 0
  assert whole.Stats()["moe_tokens_routed"] == tokens * 2 * 2


def test_the_engine_counts_the_pairs_that_ran_no_mask(tiny, monkeypatch):
  """`attend_clear_pairs` is the plans' own count of (block of the widest
  rung, page under its `clear`) pairs over the steps dispatched, in `Stats()`
  and in the step records beside `attend_live_pairs`, which it never
  passes; 0 where the twin runs and no plan is built."""
  task, theta, _ = tiny["whole"]
  twin = _Engine(task, theta, 2)
  assert twin.Stats()["attend_plans"] == 0
  monkeypatch.setattr(rba, "Lowering", lambda lowering: (
      "pallas" if lowering == "auto" else lowering))
  monkeypatch.setattr(mla_lib.MultiHeadLatentAttention, "_Lowering",
                      lambda self, page_size: "pallas")
  eng = _Engine(task, theta, 2)
  (key,) = {k for k in task.stack.RaggedPlanKeys(eng._states) if k.kernel}
  assert key.clear and eng.Stats()["attend_plans"] == 1
  seen, note = [], eng._NoteDispatch

  def _Note(batch):
    seen.append((np.array(batch.rows_desc.row_q_pos),
                 np.array(batch.rows_desc.row_len)))
    return note(batch)

  monkeypatch.setattr(eng, "_NoteDispatch", _Note)
  long = eng.Submit(np.arange(1, 40, dtype=np.int32), 3)
  eng.Submit(np.arange(1, 9, dtype=np.int32), 3)
  while not long.done:
    eng.StepOnce()
  st = eng.Stats()
  pages = eng.sched.table_pages
  assert st["attend_clear_pairs"] == sum(
      rba.ClearPairs(key, q_pos, n, pages) for q_pos, n in seen)
  assert st["attend_live_pairs"] == sum(
      rba.LivePairs(key, q_pos, n, pages) for q_pos, n in seen)
  assert 0 < st["attend_clear_pairs"] < st["attend_live_pairs"]
  last = [r for r in eng.trace.Steps() if r.counters][-1].counters
  assert (last["attend_clear_pairs"], last["attend_live_pairs"]) == (
      st["attend_clear_pairs"], st["attend_live_pairs"])
  assert twin.Stats()["attend_clear_pairs"] == 0


# -- the published depth, from shapes ------------------------------------------


def test_the_published_model_counts_its_parameters():
  """118,972,826,624 parameters at the published depth (published 119B) and
  6.6B active a token with the embedding and the head (published A6.5B), from
  the shapes of the variables the registered model would make."""
  task = _Task("MistralSmall4")
  specs = jax.eval_shape(task.InstantiateVariables, jax.random.PRNGKey(0))
  count = lambda tree: sum(int(np.prod(x.shape))
                           for x in jax.tree_util.tree_leaves(tree))
  body = specs.stack.body
  atten = count(body.self_atten.atten) // 36
  assert atten == (4096 * 1024 + 1024 + 1024 * 4096 + 4096 * 320 + 256
                   + 256 * 32 * 192 + 4096 * 4096) == 28_050_688
  ff = body.fflayer
  expert = 3 * 4096 * 2048
  assert count({k: ff[k] for k in ("w_gate", "w_up", "w_down")}
               ) == 36 * 128 * expert
  outside = atten + 3 * 4096 * 2048 + 4096 * 128 + 2 * 4096
  assert outside == 53_748_992
  assert count(body) == 36 * (outside + 128 * expert)
  assert count(specs) == 118_972_826_624
  active = 36 * (outside + 4 * expert) + 2 * 131072 * 4096 + 4096
  assert 6.5e9 < active < 6.7e9
  # one of four chips that share each layer, six layers, a quarter of the
  # vocabulary: the cell's share
  share = _Task("MistralSmall4", **_Share(0, 32), num_layers=6,
                vocab_size=32768)
  held = jax.eval_shape(share.InstantiateVariables, jax.random.PRNGKey(0))
  assert count(held.stack.body) == 6 * (outside + 32 * expert) == 6 * 859_055_360
  assert held.stack.body.fflayer.w_router.shape == (6, 4096, 128)
  assert held.stack.body.fflayer.w_up.shape == (6, 32, 4096, 2048)
  assert held.stack.body.fflayer.w_up.dtype == jnp.bfloat16


# -- the latent pool in the engine ---------------------------------------------


def test_the_census_prices_the_latent_row_and_the_layout_finds_it(tiny):
  task, theta, _ = tiny["share"]
  census = kv_cache.StackCensus(task)
  # 24 values a token a layer in f32, two layers
  assert census["kv_bytes_per_token"] == 2 * 24 * 4
  assert census["num_attention"] == 2 and census["num_ssm"] == 0
  assert census["kv_cache_dtype"] == "float32"
  layout = state_layout.Detect(task, theta, 9, 8, 2)
  states = task.InitPagedDecodeState(theta, 9, 8, 2)
  by_name = {"/".join(str(getattr(k, "key", k)) for k in path): axes
             for (path, _), axes in zip(
                 jax.tree_util.tree_flatten_with_path(states)[0],
                 layout.leaves)}
  assert by_name["body/self_atten/latent"] == state_layout.LeafAxes(1, 2, None)
  assert by_name["body/fflayer/routed"] == state_layout.LeafAxes(
      None, None, None)
  assert by_name["body/fflayer/elsewhere"] == state_layout.LeafAxes(
      None, None, None)
  # the published widths: 320 values stored at 384, 768 B a token a layer
  # in bf16 (640 B of them values), six layers
  full = _Task("MistralSmall4", **_Share(0, 32), num_layers=6,
               vocab_size=32768, fprop_dtype=jnp.bfloat16)
  assert kv_cache.StackCensus(full)["kv_bytes_per_token"] == 6 * 768
  assert mla_lib.StoredRow(256, 64) * 2 == 768 and (256 + 64) * 2 == 640


def test_copy_spill_and_restore_carry_the_latent_rows(tiny):
  """state_layout moves the latent leaf by page and by token without being
  told of it; a row spilled and restored mid-prompt streams what it
  streamed undisturbed."""
  task, theta, _ = tiny["share"]
  layout = state_layout.Detect(task, theta, 9, 8, 2)
  states = task.InitPagedDecodeState(theta, 9, 8, 2)
  pool = jnp.asarray(np.random.RandomState(0).randn(2, 9, 8, 24), jnp.float32)
  states.body.self_atten.latent = pool
  blocks = layout.Gather(states, "page", jnp.asarray([3, 5]))
  assert [b.shape for b in blocks] == [(2, 2, 8, 24)]
  moved = layout.Scatter(states, "page", jnp.asarray([0, 1]), blocks)
  np.testing.assert_array_equal(np.asarray(moved.body.self_atten.latent[:, 1]),
                                np.asarray(pool[:, 5]))
  copied = layout.Copy(states, "token", (jnp.asarray([2]), jnp.asarray([7])),
                       (jnp.asarray([6]), jnp.asarray([0])))
  np.testing.assert_array_equal(
      np.asarray(copied.body.self_atten.latent[:, 6, 0]),
      np.asarray(pool[:, 2, 7]))
  prompt = np.random.RandomState(5).randint(1, 128, 60).astype(np.int32)
  _, _, clean = _Serve(task, theta, [prompt], 6)

  def _SpillRestore(eng, step):
    if step != 2:
      return
    seq = next(s for s in eng.sched.slots if s is not None)
    pages = list(eng.alloc.PagesOf(seq.id)[:-(-seq.pos // 8)])
    idx = jnp.asarray(pages, jnp.int32)
    saved = [np.asarray(b) for b in eng._Layout().gather(
        eng._states, "page", idx)]
    eng._states.body.self_atten.latent = (
        eng._states.body.self_atten.latent.at[:, idx].set(jnp.nan))
    eng._states = eng._Layout().scatter(
        eng._states, "page", idx, [jnp.asarray(b) for b in saved])

  _, _, outs = _Serve(task, theta, [prompt], 6, between=_SpillRestore)
  assert outs == clean


def test_a_prefix_cache_shares_latent_pages(tiny):
  """An attention-only stack: the second request of a prompt takes the
  first's pages from the cache and streams the same tokens."""
  task, theta, _ = tiny["share"]
  prompt = np.random.RandomState(7).randint(1, 128, 50).astype(np.int32)
  _, _, alone = _Serve(task, theta, [prompt], 4)
  eng = _Engine(task, theta, 2, prefix_cache=True)
  outs = []
  for _ in range(2):
    handle = eng.Submit(prompt, 4)
    while not handle.done:
      eng.StepOnce()
    outs.append(handle.Result())
  assert outs == [alone[0], alone[0]]
  assert eng.Stats()["prefix_cache"]["hit_tokens"] >= 48


def test_priority_mode_preempts_and_restores_a_latent_row(tiny):
  task, theta, _ = tiny["share"]
  prompt = np.random.RandomState(7).randint(1, 128, 40).astype(np.int32)
  _, _, alone = _Serve(task, theta, [prompt], 6)
  eng = _Engine(task, theta, 1, num_pages=8, scheduler_mode="priority")
  low = eng.Submit(prompt, 6, priority=0)
  for _ in range(3):
    eng.StepOnce()
  high = eng.Submit(prompt[:20], 2, priority=5)
  for _ in range(200):
    if low.done and high.done:
      break
    eng.StepOnce()
  assert low.done and high.done
  assert low.Result() == alone[0]
  assert eng.Stats()["scheduler"]["preemptions"] >= 1


@pytest.mark.parametrize("kw,error,says", [
    (dict(kv_cache_dtype="int8"), NotImplementedError,
     "latent pool is in the fprop dtype"),
    (dict(spec=spec_decode.SelfDraft(k=2, num_layers=1)), ValueError,
     "MultiHeadLatentAttention serves through the packed step alone"),
])
def test_what_the_engine_cannot_do_over_a_latent_pool_it_refuses_by_name(
    tiny, kw, error, says):
  task, theta, _ = tiny["share"]
  with pytest.raises(error, match=says):
    _Engine(task, theta, 2, **kw)


@pytest.mark.parametrize("method", ["InitStates", "ExtendStep", "Prefill",
                                    "PagedStep"])
def test_the_mixer_has_no_dense_decode_contract(method):
  """None of its own (BaseLayer's ExtendStep says "does not support
  incremental decoding"), and the class says so where the engine looks."""
  layer, _ = _Mixer()
  assert method not in vars(type(layer)) and layer.ragged_only
  if method == "ExtendStep":
    with pytest.raises(NotImplementedError, match="MultiHeadLatentAttention"):
      layer.ExtendStep(None)


def test_a_window_is_refused_by_name_and_a_block_sequence_takes_a_share():
  with pytest.raises(AssertionError, match="no window"):
    _Mixer(window=16)
  # refused until PR 57 ("holds every expert it routes over"): the layer of
  # a BlockSequence now carries the share's `elsewhere` leaf beside `routed`
  from lingvo_tpu.core import moe as moe_lib
  from lingvo_tpu.core import transformer as transformer_lib
  p = transformer_lib.SharedStateLayer.Params().Set(
      name="layer", input_dim=48, mixer_tpl=None,
      tr_fflayer_tpl=moe_lib.DroplessMoELayer.Params().Set(
          hidden_dim=8, num_experts=8, num_experts_held=2,
          router_reads="normed_input"))
  layer = p.Instantiate()
  layer.FinalizePaths()
  theta = layer.InstantiateVariables(jax.random.PRNGKey(0))
  states = layer.InitPagedStates(theta, 4)
  assert states.routed.shape == (2,) and states.elsewhere.shape == ()
