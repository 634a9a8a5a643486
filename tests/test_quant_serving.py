"""Quantized serving subsystem (lingvo_tpu/quant/, docs/quantized_serving.md).

Covers the numerics contract end to end:
- `Int8QuantizeWeight`/`Int8Einsum` under both 'dv' and 'vd' layouts (and
  the legacy all-but-last default), `Int8Weight` as a jit-transparent
  pytree leaf,
- `QuantizeKv` per-token-per-head symmetric quantization error bounds and
  the `KvBytesPerToken` accounting (incl. the >= 1.8x bf16 -> int8 ratio
  at serving head dims),
- the int8 block-table decode kernels: the XLA twin is BITWISE equal to
  the Pallas(interpret) twin — including after the allocator frees pages
  and hands them to another sequence — and both are bitwise equal to the
  float kernel run on the dequantized pools (dequantize-on-read is the
  only difference between the paths),
- quantized `BlockPrefill` against the same dequantized-pool float run,
- the dense (non-paged) int8 cache: ExtendStep/Prefill parity with float,
- the serving engine with kv_cache_dtype='int8' (+ serve_int8_weights):
  greedy token parity with the f32 engine, Stats() visibility
  (kv_cache_dtype / kv_bytes_per_token / quantized_steps / pool_bytes),
  dense-fallback visibility for ineligible configs, and default-off
  bit-exactness (no sidecars allocated, legacy path classification),
- the export round trip: Export(quantize_int8=True) ->
  Predictor.Int8ServingTheta('dequant') is bitwise the frozen theta
  (ScoreSequences bitwise equal), mode='int8' has a bounded delta, and the
  manifest records per-leaf layouts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu.core import quant_utils
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.ops import block_decode
from lingvo_tpu.quant import kv as kv_quant
from lingvo_tpu.quant import weights as quant_weights
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import kv_cache


def _TinyLmParams(**overrides):
  from lingvo_tpu.models.lm import layers as lm_layers
  p = lm_layers.TransformerLm.Params().Set(
      name="lm", vocab_size=64, model_dim=32, num_layers=2, num_heads=2,
      hidden_dim=64, use_rotary=True)
  return p.Set(**overrides)


@pytest.fixture(scope="module")
def tiny_lm():
  task = _TinyLmParams().Instantiate()
  task.FinalizePaths()
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))
  return task, theta


# -- weight quantization -----------------------------------------------------


class TestInt8Weights:

  def test_dv_layout_einsum_close_to_float(self):
    rng = np.random.RandomState(0)
    x = rng.randn(3, 5, 16).astype(np.float32)
    w = rng.randn(16, 2, 8).astype(np.float32)       # [D, N, H], contract D
    w8, scale = quant_utils.Int8QuantizeWeight(
        jnp.asarray(w), layout="dv", contract_ndim=1)
    assert w8.shape == w.shape and scale.shape == (1, 2, 8)
    out = quant_utils.Int8Einsum(jnp.asarray(x), w8, scale,
                                 layout="dv", contract_ndim=1)
    ref = np.einsum("btd,dnh->btnh", x, w)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), ref,
                               atol=0.05 * np.abs(ref).max())

  def test_vd_layout_einsum_close_to_float(self):
    rng = np.random.RandomState(1)
    x = rng.randn(3, 2, 8).astype(np.float32)        # [B, N, H]
    w = rng.randn(2, 8, 16).astype(np.float32)       # [N, H, D], contract N,H
    # NOTE: 'vd' means the contraction axes TRAIL — transpose to [D, N, H]?
    # No: w_post's einsum "BNH,NHD->BD" contracts the LEADING axes of w
    # when stored [N, H, D]... the serving layout stores w_post [D, N, H]
    # ('vd', 2): output axis leads, the 2 contraction axes trail.
    w_vd = np.transpose(w, (2, 0, 1))                # [D, N, H]
    w8, scale = quant_utils.Int8QuantizeWeight(
        jnp.asarray(w_vd), layout="vd", contract_ndim=2)
    assert w8.shape == w_vd.shape and scale.shape == (16, 1, 1)
    out = quant_utils.Int8Einsum(jnp.asarray(x), w8, scale,
                                 layout="vd", contract_ndim=2)
    ref = np.einsum("bnh,dnh->bd", x, w_vd)
    np.testing.assert_allclose(np.asarray(out), ref,
                               atol=0.05 * np.abs(ref).max())

  def test_legacy_default_matches_explicit_dv(self):
    """The pre-layout 3-arg call (all-but-last reduction) must keep its
    meaning: for a 2-D [in, out] weight it equals ('dv', 1)."""
    rng = np.random.RandomState(2)
    x = rng.randn(4, 6).astype(np.float32)
    w = rng.randn(6, 10).astype(np.float32)
    w8a, sa = quant_utils.Int8QuantizeWeight(jnp.asarray(w))
    w8b, sb = quant_utils.Int8QuantizeWeight(jnp.asarray(w), layout="dv",
                                             contract_ndim=1)
    np.testing.assert_array_equal(np.asarray(w8a), np.asarray(w8b))
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
    out_a = quant_utils.Int8Einsum(jnp.asarray(x), w8a, sa)
    out_b = quant_utils.Int8Einsum(jnp.asarray(x), w8b, sb,
                                   layout="dv", contract_ndim=1)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))

  def test_int8weight_is_jit_transparent_pytree(self):
    rng = np.random.RandomState(3)
    w = rng.randn(8, 12).astype(np.float32)
    x = rng.randn(2, 8).astype(np.float32)
    node = quant_utils.Int8Weight.Quantize(jnp.asarray(w), layout="dv",
                                           contract_ndim=1)
    leaves, treedef = jax.tree_util.tree_flatten(node)
    assert len(leaves) == 2      # (w_int8, scale); layout rides as aux
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.layout == "dv" and rebuilt.contract_ndim == 1
    eager = node.Einsum(jnp.asarray(x))
    jitted = jax.jit(lambda n, xx: n.Einsum(xx))(node, jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(jitted))
    np.testing.assert_allclose(np.asarray(node.Dequant()), w,
                               atol=np.abs(w).max() / 127)

  def test_stacked_repeated_leaves_get_per_repeat_scales(self):
    """A Repeated stack's `.body.` weight [reps, ...] must quantize each
    repeat independently — the repeat axis is batch, not contraction."""
    rng = np.random.RandomState(4)
    w = rng.randn(3, 8, 12).astype(np.float32)       # [reps, in, out]
    w[1] *= 100.0                                    # wildly different range
    node = quant_weights.QuantizeLeafInt8(jnp.asarray(w), "dv", 1,
                                          stacked=True)
    assert node.w_int8.shape == (3, 8, 12)
    assert node.scale.shape == (3, 1, 12)
    # per-repeat scales: repeat 1's huge range cannot poison repeat 0
    per_rep = [quant_utils.Int8Weight.Quantize(jnp.asarray(w[i]),
                                               layout="dv", contract_ndim=1)
               for i in range(3)]
    for i in range(3):
      np.testing.assert_array_equal(np.asarray(node.w_int8[i]),
                                    np.asarray(per_rep[i].w_int8))
    np.testing.assert_allclose(np.asarray(node.Dequant()), w,
                               atol=np.abs(w[1]).max() / 127)

  def test_serving_theta_rewrites_only_table_leaves(self, tiny_lm):
    task, theta = tiny_lm
    t8, paths = quant_weights.Int8ServingTheta(theta)
    for path in paths:
      assert path.rsplit(".", 1)[-1] in quant_weights.SERVING_WEIGHT_LAYOUTS
      assert isinstance(t8.Get(path), quant_utils.Int8Weight)
    # non-table leaves (biases, layer norm) are untouched
    for path, leaf in theta.FlattenItems():
      if path not in paths:
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(t8.Get(path)))


# -- KV quantization ---------------------------------------------------------


class TestKvQuant:

  def test_roundtrip_error_bounded_by_half_scale(self):
    rng = np.random.RandomState(0)
    x = (rng.randn(5, 7, 4, 16) * rng.lognormal(size=(5, 7, 4, 1))
         ).astype(np.float32)
    q, scale = kv_quant.QuantizeKv(jnp.asarray(x))
    back = kv_quant.DequantKv(q, scale)
    err = np.abs(np.asarray(back) - x)
    bound = np.asarray(scale)[..., None] * 0.5 + 1e-6
    assert (err <= bound).all()

  def test_all_zero_rows_quantize_and_dequantize_to_zero(self):
    q, scale = kv_quant.QuantizeKv(jnp.zeros((2, 3, 8)))
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(np.asarray(kv_quant.DequantKv(q, scale)), 0)

  def test_resolve_dtype_defaults_and_validation(self):
    dt, quant = kv_quant.ResolveKvCacheDtype(None, jnp.bfloat16)
    assert dt == jnp.bfloat16 and not quant
    dt, quant = kv_quant.ResolveKvCacheDtype("int8", jnp.float32)
    assert dt == jnp.int8 and quant
    dt, quant = kv_quant.ResolveKvCacheDtype("bfloat16", jnp.float32)
    assert dt == jnp.bfloat16 and not quant
    with pytest.raises(ValueError, match="kv_cache_dtype"):
      kv_quant.ResolveKvCacheDtype("int4", jnp.float32)

  def test_bytes_per_token_and_compression_ratio(self):
    # serving head dim (H=64): f32 2048, bf16 1024, int8 544 per layer
    n, h = 4, 64
    f32 = kv_quant.KvBytesPerToken(n, h, None, jnp.float32)
    bf16 = kv_quant.KvBytesPerToken(n, h, "bfloat16", jnp.float32)
    i8 = kv_quant.KvBytesPerToken(n, h, "int8", jnp.float32)
    assert (f32, bf16, i8) == (2048, 1024, 544)
    # the ISSUE's fixed-HBM admission criterion: int8 must fit >= 1.8x the
    # sequences a bf16 cache fits
    assert bf16 / i8 >= 1.8

  def test_stack_census_counts_repeated_layers(self, tiny_lm):
    task, _ = tiny_lm
    census = kv_cache.StackCensus(task)
    # 2 repeated layers x (2 heads * 16 dim * 2(K,V) * 4B) = 512 B/token
    assert census == {"kv_cache_dtype": "float32",
                      "kv_bytes_per_token": 512, "attention_layers": 2,
                      "num_attention": 2, "num_ssm": 0,
                      "decode_state_bytes_per_slot": 0}
    census8 = kv_cache.StackCensus(task, "int8")
    assert census8["kv_cache_dtype"] == "int8"
    # per layer: 2*2*16*1 + 2*2*4 = 80 -> 160 total
    assert census8["kv_bytes_per_token"] == 160


# -- int8 kernel twins -------------------------------------------------------


def _QuantizePools(k_pool, v_pool):
  """float pools [NP, P, N, H] -> int8 pools + TRANSPOSED [NP, N, P]
  sidecars (the device layout attention.InitPagedStates allocates)."""
  k8, ks = kv_quant.QuantizeKv(jnp.asarray(k_pool))   # scale [NP, P, N]
  v8, vs = kv_quant.QuantizeKv(jnp.asarray(v_pool))
  return (k8, jnp.swapaxes(ks, 1, 2).astype(jnp.float32),
          v8, jnp.swapaxes(vs, 1, 2).astype(jnp.float32))


def _DequantPools(k8, ks, v8, vs):
  """The float pools an int8 run must reproduce bitwise: elementwise
  dequantization in the same [NP, P, N, H] layout."""
  kf = kv_quant.DequantKv(k8.swapaxes(1, 2), ks).swapaxes(1, 2)
  vf = kv_quant.DequantKv(v8.swapaxes(1, 2), vs).swapaxes(1, 2)
  return kf, vf


class TestInt8KernelTwins:

  def _Inputs(self, b=2, t_pages=2, page=8, n=1, h=8, seed=0):
    rng = np.random.RandomState(seed)
    np_total = b * t_pages + 1
    q = rng.randn(b, 1, n, h).astype(np.float32)
    k_pool = rng.randn(np_total, page, n, h).astype(np.float32)
    v_pool = rng.randn(np_total, page, n, h).astype(np.float32)
    tables = rng.permutation(np_total - 1).reshape(b, t_pages).astype(
        np.int32)
    return q, k_pool, v_pool, tables

  def test_int8_twins_bitwise_and_match_float_on_dequant_grid(self):
    """int8 XLA == int8 Pallas(interpret) bitwise, and both == the float
    kernel run on the dequantized pools bitwise: dequantize-on-read is the
    ONLY thing the quantized path adds."""
    q, k_pool, v_pool, tables = self._Inputs()
    k8, ks, v8, vs = _QuantizePools(k_pool, v_pool)
    kf, vf = _DequantPools(k8, ks, v8, vs)
    for lens in ([0, 16], [5, 16], [1, 9], [8, 8]):
      ln = jnp.asarray(lens, jnp.int32)
      out_x = block_decode.BlockDecode(
          jnp.asarray(q), k8, v8, jnp.asarray(tables), ln, page_size=8,
          k_scale=ks, v_scale=vs, lowering="xla")
      out_p = block_decode.BlockDecode(
          jnp.asarray(q), k8, v8, jnp.asarray(tables), ln, page_size=8,
          k_scale=ks, v_scale=vs, lowering="pallas", interpret=True)
      out_f = block_decode.BlockDecode(
          jnp.asarray(q), kf, vf, jnp.asarray(tables), ln, page_size=8,
          lowering="xla")
      np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_p))
      np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_f))

  def test_int8_twins_bitwise_after_page_reuse(self):
    """The eviction scenario: a real allocator frees one sequence's pages,
    hands them to another, and the new tokens overwrite the int8 pages AND
    their scale sidecars in place. Twins must stay bitwise equal."""
    q, k_pool, v_pool, tables = self._Inputs()
    k8, ks, v8, vs = _QuantizePools(k_pool, v_pool)

    def _Both(ln_np, tb):
      ln = jnp.asarray(ln_np, jnp.int32)
      out_x = block_decode.BlockDecode(
          jnp.asarray(q), k8, v8, jnp.asarray(tb), ln, page_size=8,
          k_scale=ks, v_scale=vs, lowering="xla")
      out_p = block_decode.BlockDecode(
          jnp.asarray(q), k8, v8, jnp.asarray(tb), ln, page_size=8,
          k_scale=ks, v_scale=vs, lowering="pallas", interpret=True)
      np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_p))
      return np.asarray(out_x)

    before = _Both([5, 16], tables)

    alloc = kv_cache.PageAllocator(num_pages=4, page_size=8)
    alloc.Allocate("a", 2)
    alloc.Allocate("b", 2)
    alloc.Free("a")
    reused = alloc.Allocate("c", 2)
    assert reused == [0, 1]
    rng = np.random.RandomState(7)
    for pg in reused:
      # quantize-on-write: fresh tokens land as int8 + new per-slot scales
      fresh_k = rng.randn(8, 1, 8).astype(np.float32) * 3.0
      fresh_v = rng.randn(8, 1, 8).astype(np.float32) * 3.0
      fk8, fks = kv_quant.QuantizeKv(jnp.asarray(fresh_k))
      fv8, fvs = kv_quant.QuantizeKv(jnp.asarray(fresh_v))
      k8 = k8.at[pg].set(fk8)
      ks = ks.at[pg].set(jnp.swapaxes(fks, 0, 1))
      v8 = v8.at[pg].set(fv8)
      vs = vs.at[pg].set(jnp.swapaxes(fvs, 0, 1))
    tables2 = np.array([reused, list(alloc.PagesOf("b"))], np.int32)
    after = _Both([12, 16], tables2)
    # the overwrite actually changed what row 0 attends to
    assert not np.array_equal(before[0], after[0])
    # and the float-on-dequant-grid equality still holds post-reuse
    kf, vf = _DequantPools(k8, ks, v8, vs)
    out_f = block_decode.BlockDecode(
        jnp.asarray(q), kf, vf, jnp.asarray(tables2),
        jnp.asarray([12, 16], jnp.int32), page_size=8, lowering="xla")
    np.testing.assert_array_equal(after, np.asarray(out_f))

  def test_int8_block_prefill_matches_float_on_dequant_grid(self):
    b, c, n, h, page, t_pages = 2, 4, 2, 8, 4, 3
    rng = np.random.RandomState(3)
    np_total = b * t_pages + 1
    q = rng.randn(b, c, n, h).astype(np.float32)
    k_pool = rng.randn(np_total, page, n, h).astype(np.float32)
    v_pool = rng.randn(np_total, page, n, h).astype(np.float32)
    tables = rng.permutation(np_total - 1).reshape(b, t_pages).astype(
        np.int32)
    k8, ks, v8, vs = _QuantizePools(k_pool, v_pool)
    kf, vf = _DequantPools(k8, ks, v8, vs)
    q_pos = jnp.asarray([0, 5], jnp.int32)
    in_len = jnp.asarray([4, 3], jnp.int32)
    out8 = block_decode.BlockPrefill(
        jnp.asarray(q), k8, v8, jnp.asarray(tables), q_pos, in_len,
        page_size=page, k_scale=ks, v_scale=vs)
    outf = block_decode.BlockPrefill(
        jnp.asarray(q), kf, vf, jnp.asarray(tables), q_pos, in_len,
        page_size=page)
    np.testing.assert_array_equal(np.asarray(out8), np.asarray(outf))

  def test_gather_scales_layout(self):
    scales = jnp.arange(2 * 3 * 4, dtype=jnp.float32).reshape(2, 3, 4)
    tables = jnp.asarray([[1, 0]], jnp.int32)
    out = block_decode.GatherScales(scales, tables)     # [1, 8, 3]
    assert out.shape == (1, 8, 3)
    # logical slot 0 = page 1 slot 0; per-head values = scales[1, :, 0]
    np.testing.assert_array_equal(np.asarray(out[0, 0]),
                                  np.asarray(scales[1, :, 0]))
    np.testing.assert_array_equal(np.asarray(out[0, 4]),
                                  np.asarray(scales[0, :, 0]))


# -- dense (non-paged) int8 cache --------------------------------------------


class TestDenseCacheInt8:

  @pytest.fixture(scope="class")
  def int8_lm(self):
    task = _TinyLmParams(kv_cache_dtype="int8").Instantiate()
    task.FinalizePaths()
    theta = task.InstantiateVariables(jax.random.PRNGKey(0))
    return task, theta

  def test_init_states_carry_scale_sidecars(self, tiny_lm, int8_lm):
    task8, theta8 = int8_lm
    states = task8.InitDecodeState(theta8, 2, 16)
    leaves = {p for p, _ in states.FlattenItems()}
    assert any("key_scale" in p for p in leaves)
    assert any(l.dtype == jnp.int8 for _, l in states.FlattenItems()
               if hasattr(l, "dtype"))
    task, theta = tiny_lm
    legacy = task.InitDecodeState(theta, 2, 16)
    assert not any("key_scale" in p for p, _ in legacy.FlattenItems())

  def test_extend_step_greedy_matches_float(self, tiny_lm, int8_lm):
    """Same theta, int8 vs float dense cache: logits stay close and the
    greedy continuation is identical on a fixed prompt."""
    task, theta = tiny_lm
    task8, _ = int8_lm
    prompt = [5, 9, 2, 33, 17]

    def _Roll(tk):
      states = tk.InitDecodeState(theta, 1, 12)
      ext = jax.jit(lambda th, ids, st: tk.ExtendStep(th, ids, st))
      logits = None
      for t in prompt:
        logits, states = ext(theta, jnp.asarray([[t]], jnp.int32), states)
      toks, lgs = [], []
      for _ in range(5):
        nxt = int(np.argmax(np.asarray(logits[0])))
        toks.append(nxt)
        lgs.append(np.asarray(logits[0]))
        logits, states = ext(theta, jnp.asarray([[nxt]], jnp.int32), states)
      return toks, np.stack(lgs)

    toks_f, lg_f = _Roll(task)
    toks_8, lg_8 = _Roll(task8)
    assert toks_f == toks_8
    np.testing.assert_allclose(lg_8, lg_f, atol=0.05 * np.abs(lg_f).max())

  def test_prefill_matches_float_closely(self, tiny_lm, int8_lm):
    task, theta = tiny_lm
    task8, _ = int8_lm
    ids = jnp.asarray([[5, 9, 2, 33, 17, 4]], jnp.int32)
    states = task.InitDecodeState(theta, 1, 8)
    logits_f, _ = jax.jit(task.Prefill)(theta, ids, states)
    states8 = task8.InitDecodeState(theta, 1, 8)
    logits_8, _ = jax.jit(task8.Prefill)(theta, ids, states8)
    np.testing.assert_allclose(
        np.asarray(logits_8), np.asarray(logits_f),
        atol=0.05 * np.abs(np.asarray(logits_f)).max())


# -- quantized serving engine ------------------------------------------------


class TestQuantizedEngine:

  _PROMPTS = np.array([[5, 9, 2, 33, 17], [7, 7, 7, 0, 0]], np.int32)
  _LENS = np.array([5, 3], np.int32)

  def _Engine(self, task, theta, **kw):
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 16)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 16)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("default_max_new", 4)
    return engine_lib.ServingLoop(task, theta, **kw)

  def test_int8_engine_token_parity_and_stats(self, tiny_lm):
    task, theta = tiny_lm
    eng_f = self._Engine(task, theta)
    eng_8 = self._Engine(task, theta, kv_cache_dtype="int8")
    out_f = eng_f.RunBatch(self._PROMPTS, self._LENS, 4)
    out_8 = eng_8.RunBatch(self._PROMPTS, self._LENS, 4)
    np.testing.assert_array_equal(out_f, out_8)

    sf, s8 = eng_f.Stats(), eng_8.Stats()
    from lingvo_tpu.observe import schema as observe_schema
    observe_schema.ValidateEngineStats(sf)
    observe_schema.ValidateEngineStats(s8)
    base = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert sf["paged_path"] == base
    assert sf["kv_cache_dtype"] == "float32"
    assert sf["quantized_steps"] == 0
    assert s8["paged_path"] == base + "-int8"
    assert s8["kv_cache_dtype"] == "int8"
    assert s8["quantized_steps"] == s8["steps"] > 0
    assert s8["dense_fallback_steps"] == 0
    # honest HBM accounting: per-token bytes shrink ~3.2x, pool bytes match
    assert sf["kv_bytes_per_token"] == 512 and s8["kv_bytes_per_token"] == 160
    assert s8["kv_pages"]["pool_bytes"] == 160 * 4 * 16
    # the quantized pool really is int8 + sidecars on device
    leaves = list(eng_8._states.FlattenItems())
    assert any(hasattr(l, "dtype") and l.dtype == jnp.int8
               for _, l in leaves)
    assert any("key_scale" in p for p, _ in leaves)

  def test_default_off_allocates_no_sidecars(self, tiny_lm):
    """kv_cache_dtype unset = the bit-exact legacy engine: float pool, no
    scale sidecars, legacy path name, zero quantized steps."""
    task, theta = tiny_lm
    eng = self._Engine(task, theta)
    leaves = list(eng._states.FlattenItems())
    assert not any("scale" in p for p, _ in leaves)
    assert not any(hasattr(l, "dtype") and l.dtype == jnp.int8
                   for _, l in leaves)

  def test_int8_weights_engine_token_parity(self, tiny_lm):
    task, theta = tiny_lm
    eng_f = self._Engine(task, theta)
    eng_w = self._Engine(task, theta, kv_cache_dtype="int8",
                         serve_int8_weights=True)
    out_f = eng_f.RunBatch(self._PROMPTS, self._LENS, 4)
    out_w = eng_w.RunBatch(self._PROMPTS, self._LENS, 4)
    np.testing.assert_array_equal(out_f, out_w)
    sw = eng_w.Stats()
    assert sw["serve_int8_weights"] is True
    assert sw["quantized_steps"] == sw["steps"] > 0

  def test_ineligible_int8_config_falls_back_dense_and_visibly(self):
    """atten_logit_cap fails the eligibility gate with a quantized pool
    too: the engine still serves the int8 pages (gather + dequantize +
    dense attention) and reports 'dense', never silently."""
    from lingvo_tpu.core import attention as attention_lib
    p = _TinyLmParams()
    p.atten_tpl = attention_lib.MultiHeadedAttention.Params().Set(
        atten_logit_cap=50.0)
    task = p.Instantiate()
    task.FinalizePaths()
    theta = task.InstantiateVariables(jax.random.PRNGKey(0))
    eng_d = self._Engine(task, theta)                       # float dense ref
    eng_8 = self._Engine(task, theta, kv_cache_dtype="int8")
    assert eng_8.paged_path == "dense"
    out_d = eng_d.RunBatch(self._PROMPTS, self._LENS, 4)
    out_8 = eng_8.RunBatch(self._PROMPTS, self._LENS, 4)
    np.testing.assert_array_equal(out_d, out_8)
    s8 = eng_8.Stats()
    from lingvo_tpu.observe import schema as observe_schema
    observe_schema.ValidateEngineStats(s8)
    assert s8["paged_path"] == "dense"
    assert s8["kv_cache_dtype"] == "int8"
    assert s8["dense_fallback_steps"] == s8["steps"] > 0
    assert s8["quantized_steps"] == s8["steps"]


# -- export round trip -------------------------------------------------------


class TestInt8ExportRoundTrip:

  def test_export_predict_int8_serving_theta(self, tiny_lm, tmp_path):
    from lingvo_tpu.serving import export as export_lib
    task, theta = tiny_lm
    export_dir = str(tmp_path / "export_int8")
    manifest = export_lib.InferenceGraphExporter.Export(
        task, theta, export_dir, quantize_int8=True)
    # the manifest records how every artifact leaf was laid out
    assert set(manifest["int8_layouts"]) == set(manifest["int8_weights"])
    lay = manifest["int8_layouts"]
    assert lay["emb.emb"] == {"layout": "vd", "contract_ndim": 1,
                              "stacked": False, "serving_eligible": True}
    atten = "stack.body.self_atten.atten."
    assert lay[atten + "w_post"]["layout"] == "vd"
    assert lay[atten + "w_post"]["contract_ndim"] == 2
    assert lay[atten + "w_query"] == {"layout": "dv", "contract_ndim": 1,
                                      "stacked": True,
                                      "serving_eligible": True}

    pred = export_lib.Predictor(export_dir)
    frozen = pred._theta
    ids = np.array([[5, 9, 2, 33, 17, 4, 8, 1]], np.int32)
    batch = NestedMap(ids=jnp.asarray(ids),
                      labels=jnp.asarray(np.roll(ids, -1, axis=1)),
                      paddings=jnp.zeros(ids.shape, jnp.float32))
    score = jax.jit(task.ScoreSequences)

    # freeze contract (export.py Export/QuantizeThetaInt8): the dequant-mode
    # serving theta IS the frozen theta, bit for bit — so scoring through it
    # matches the frozen-float export bitwise
    th_dq = pred.Int8ServingTheta(mode="dequant")
    for path, leaf in frozen.FlattenItems():
      np.testing.assert_array_equal(np.asarray(leaf),
                                    np.asarray(th_dq.Get(path)), err_msg=path)
    s_frozen = score(frozen, batch)
    s_dq = score(th_dq, batch)
    np.testing.assert_array_equal(np.asarray(s_frozen.label_log_probs),
                                  np.asarray(s_dq.label_log_probs))

    # integer-matmul mode: bounded, reported delta vs the frozen export
    th_i8 = pred.Int8ServingTheta(mode="int8")
    s_i8 = score(th_i8, batch)
    delta = np.abs(np.asarray(s_i8.label_log_probs) -
                   np.asarray(s_frozen.label_log_probs))
    assert float(delta.mean()) < 0.1 and float(delta.max()) < 0.5

  def test_gshard_decode_serve_int8_weights(self, tmp_path):
    """The batch-synchronous driver serves int8 weights behind the same
    flag and reports it (plus the KV census) in telemetry."""
    from lingvo_tpu.core import checkpointer as checkpointer_lib
    from lingvo_tpu.runners import gshard_decode
    from lingvo_tpu import model_registry
    import lingvo_tpu.models.all_params  # noqa: F401

    mp = model_registry.GetParams("lm.synthetic_packed_input.DenseLmTiny",
                                  "Train")
    mp.task.input = mp.input
    task = mp.task.Instantiate()
    task.FinalizePaths()
    train_dir = str(tmp_path / "train")
    ckpt = checkpointer_lib.Checkpointer(train_dir)
    state = task.CreateTrainState(jax.random.PRNGKey(3))
    ckpt.Save(1, state, force=True)
    ckpt.Close()
    prompts = np.array([[5, 6, 7, 8], [9, 10, 0, 0]], np.int32)
    lens = np.array([4, 2], np.int32)

    d_f = gshard_decode.GShardDecode(
        task, train_dir, str(tmp_path / "f.jsonl"), max_decode_steps=4)
    d_8 = gshard_decode.GShardDecode(
        task, train_dir, str(tmp_path / "i8.jsonl"), max_decode_steps=4,
        serve_int8_weights=True)
    recs_f = d_f.DecodeOnce(1, prompts, lens)
    recs_8 = d_8.DecodeOnce(1, prompts, lens)
    for rf, r8 in zip(recs_f, recs_8):
      assert rf["output_ids"] == r8["output_ids"]
    t8 = d_8._last_telemetry
    assert t8["serve_int8_weights"] is True
    assert t8["kv_cache_dtype"] == "float32"
    assert t8["kv_bytes_per_token"] > 0
    # the rewrite is cached per checkpoint: a second call reuses it
    cached = d_8._int8_theta
    d_8.DecodeOnce(1, prompts, lens)
    assert d_8._int8_theta is cached
