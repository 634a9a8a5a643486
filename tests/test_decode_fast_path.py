"""Decode fast path: chunked prefill + length-aware paged flash decode.

Covers docs/decode_fast_path.md:
- chunked prefill writes the same KV cache as the per-token ExtendStep
  scan (layer-0 bitwise; deeper layers to float tolerance at live slots —
  the [C, S] context matmul blocks differently than C matvecs, and that
  ulp noise feeds the next layer's projections) and reproduces its logits
  at every real prompt position,
- the paged ExtendStep read (`decode_page_size`) matches the dense path,
- the flash-decode XLA twin matches a dense softmax reference and is
  bit-identical to the Pallas kernel in interpret mode (slow),
- decode-shape bucketing reuses one compiled program across ragged
  prompt widths without changing outputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu.core import attention as attention_lib
from lingvo_tpu.core import py_utils
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.ops import flash_decode


def _TinyLm(use_repeat_layer=True, use_rotary=True, decode_page_size=0):
  from lingvo_tpu.models.lm import layers as lm_layers
  p = lm_layers.TransformerLm.Params().Set(
      name="lm", vocab_size=64, model_dim=32, num_layers=2, num_heads=2,
      hidden_dim=64, use_repeat_layer=use_repeat_layer, use_rotary=use_rotary)
  if decode_page_size:
    p.atten_tpl = attention_lib.MultiHeadedAttention.Params().Set(
        decode_page_size=decode_page_size)
  task = p.Instantiate()
  task.FinalizePaths()
  return task


def _RaggedCachePaddings(p_len, total, lens):
  slot = jnp.arange(total)[None, :]
  return (slot < (p_len - lens)[:, None]).astype(jnp.float32)


class TestChunkedPrefill:

  @pytest.mark.parametrize("use_rotary", [True, False])
  @pytest.mark.parametrize("use_repeat_layer", [True, False])
  def test_prefill_matches_per_token_prime(self, use_rotary,
                                           use_repeat_layer):
    """One Prefill pass == P sequential ExtendSteps: same cache, same
    logits at real (non-left-pad) prompt positions, ragged lengths."""
    task = _TinyLm(use_repeat_layer=use_repeat_layer, use_rotary=use_rotary)
    theta = task.InstantiateVariables(jax.random.PRNGKey(0))
    b, p_len, t_max = 2, 8, 4
    total = p_len + t_max
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, p_len), 1, 64)
    lens = jnp.asarray([p_len, 5])
    pad = _RaggedCachePaddings(p_len, total, lens)

    ext = jax.jit(lambda ids_t, states: task.ExtendStep(
        theta, ids_t, states, cache_paddings=pad))
    states = task.InitDecodeState(theta, b, total)
    step_logits = []
    for t in range(p_len):
      lt, states = ext(ids[:, t:t + 1], states)
      step_logits.append(lt)
    prime_logits = jnp.stack(step_logits, 1)

    states2 = task.InitDecodeState(theta, b, total)
    pre_logits, states2 = task.Prefill(theta, ids, states2,
                                       cache_paddings=pad)

    # K/V caches: layer 0 is bitwise identical (projections + rotary are
    # per-position); deeper layers inherit ulp noise from the previous
    # layer's batched-vs-per-token context matmul. Left-pad slots hold
    # path-dependent garbage (fully-masked rows see different unwritten
    # caches) and are excluded — they are masked from attention forever.
    live = (jnp.arange(total)[None, :] >= (p_len - lens)[:, None])
    live = live.astype(jnp.float32)[:, :, None, None]      # [B, S, 1, 1]
    flat1 = jax.tree_util.tree_leaves(states)
    flat2 = jax.tree_util.tree_leaves(states2)
    for a, b_ in zip(flat1, flat2):
      if a.ndim == 5:    # repeat-layer stacked leaf [L, B, S, N, H]
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b_[0]))
        np.testing.assert_allclose(np.asarray(a * live[None]),
                                   np.asarray(b_ * live[None]), atol=1e-4)
      elif a.ndim == 4:  # per-layer leaf [B, S, N, H]
        np.testing.assert_allclose(np.asarray(a * live),
                                   np.asarray(b_ * live), atol=1e-4)
      else:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    # logits at real positions match to float tolerance; greedy
    # continuations (what the driver emits) are identical
    valid = (jnp.arange(p_len)[None, :] >= (p_len - lens)[:, None])
    err = jnp.abs(prime_logits - pre_logits) * valid[:, :, None]
    assert float(jnp.max(err)) < 2e-5, float(jnp.max(err))
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(prime_logits[:, -1], -1)),
        np.asarray(jnp.argmax(pre_logits[:, -1], -1)))

  def test_multi_chunk_prefill_matches_single_pass(self):
    task = _TinyLm()
    theta = task.InstantiateVariables(jax.random.PRNGKey(0))
    b, p_len = 2, 8
    total = 12
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, p_len), 1, 64)
    states1 = task.InitDecodeState(theta, b, total)
    one, states1 = task.Prefill(theta, ids, states1)
    states2 = task.InitDecodeState(theta, b, total)
    la, states2 = task.Prefill(theta, ids[:, :5], states2)
    lb, states2 = task.Prefill(theta, ids[:, 5:], states2)
    two = jnp.concatenate([la, lb], axis=1)
    np.testing.assert_allclose(np.asarray(one), np.asarray(two), atol=2e-5)
    # time_step advanced to p_len (leaf is [L]-shaped under repeat-layer)
    assert np.all(np.asarray(jax.tree_util.tree_leaves(states2)[1]) == p_len)

  def test_live_len_trimmed_read_matches_full_cache_read(self):
    """live_len only removes exact-zero (masked) softmax contributions, so
    the trimmed attention read must match the full-cache read, and the
    written KV cache must agree to float rounding."""
    task = _TinyLm()
    theta = task.InstantiateVariables(jax.random.PRNGKey(0))
    b, p_len, total = 2, 6, 24
    ids = jax.random.randint(jax.random.PRNGKey(1), (b, p_len), 1, 64)
    full_states = task.InitDecodeState(theta, b, total)
    full, full_states = task.Prefill(theta, ids, full_states)
    trim_states = task.InitDecodeState(theta, b, total)
    la, trim_states = task.Prefill(theta, ids[:, :4], trim_states,
                                   live_len=4)
    lb, trim_states = task.Prefill(theta, ids[:, 4:], trim_states,
                                   live_len=p_len)
    trimmed = jnp.concatenate([la, lb], axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(trimmed),
                               atol=2e-5)
    # Not bitwise: the trimmed read sums 4 and 6 softmax terms where the
    # full read sums 24 (18+ of them exact zeros), and XLA:CPU under jax
    # 0.9.0 vectorizes the two reduction lengths in different orders, so
    # layer-1 outputs — and with them the layer-2 K/V written here — differ
    # in the last bit (1.8e-7 observed). The masked terms are still exact
    # zeros; only the order of the live terms' additions changed.
    for fl, tl in zip(jax.tree_util.tree_leaves(full_states),
                      jax.tree_util.tree_leaves(trim_states)):
      np.testing.assert_allclose(np.asarray(fl), np.asarray(tl), rtol=0,
                                 atol=1e-6)

  def test_prefill_then_extend_matches_pure_extend_rollout(self):
    """End-to-end greedy: prefill + sampled ExtendSteps == all-ExtendStep."""
    task = _TinyLm()
    theta = task.InstantiateVariables(jax.random.PRNGKey(0))
    b, p_len, t_max = 2, 6, 5
    total = p_len + t_max
    ids = jax.random.randint(jax.random.PRNGKey(2), (b, p_len), 1, 64)

    ext = jax.jit(lambda ids_t, states: task.ExtendStep(theta, ids_t, states))

    def rollout(prime_fn):
      states = task.InitDecodeState(theta, b, total)
      logits, states = prime_fn(states)
      out = []
      for _ in range(t_max):
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(nxt)
        logits, states = ext(nxt[:, None], states)
      return np.stack([np.asarray(o) for o in out], 1)

    def legacy(states):
      logits = None
      for t in range(p_len):
        logits, states = ext(ids[:, t:t + 1], states)
      return logits, states

    def fast(states):
      logits, states = task.Prefill(theta, ids, states)
      return logits[:, -1, :], states

    np.testing.assert_array_equal(rollout(legacy), rollout(fast))


class TestPagedExtendStep:

  def _PrimedStates(self, task, theta, b, p_len, total):
    ids = jax.random.randint(jax.random.PRNGKey(3), (b, p_len), 1, 64)
    states = task.InitDecodeState(theta, b, total)
    logits, states = task.Prefill(theta, ids, states)
    return logits[:, -1, :], states

  def test_paged_matches_dense_extend_step(self):
    """decode_page_size > 0 reproduces the dense-cache read; page_size=0
    (default) IS the legacy branch, so existing decode tests pin it."""
    b, p_len, t_max = 2, 8, 8
    total = p_len + t_max  # 16 slots = 4 pages of 4
    dense = _TinyLm(decode_page_size=0)
    paged = _TinyLm(decode_page_size=4)
    theta = dense.InstantiateVariables(jax.random.PRNGKey(0))
    logits_d, st_d = self._PrimedStates(dense, theta, b, p_len, total)
    logits_p, st_p = self._PrimedStates(paged, theta, b, p_len, total)
    np.testing.assert_array_equal(np.asarray(logits_d), np.asarray(logits_p))
    ext_d = jax.jit(lambda i, s: dense.ExtendStep(theta, i, s))
    ext_p = jax.jit(lambda i, s: paged.ExtendStep(theta, i, s))
    for _ in range(t_max):
      nxt = jnp.argmax(logits_d, -1).astype(jnp.int32)
      logits_d, st_d = ext_d(nxt[:, None], st_d)
      logits_p, st_p = ext_p(nxt[:, None], st_p)
      np.testing.assert_allclose(np.asarray(logits_d), np.asarray(logits_p),
                                 atol=1e-5)
      np.testing.assert_array_equal(
          np.asarray(jnp.argmax(logits_d, -1)),
          np.asarray(jnp.argmax(logits_p, -1)))

  def test_non_divisible_max_len_falls_back_to_dense(self):
    # total=15 not divisible by page 4: eligibility gate must take the
    # dense branch rather than crash
    task = _TinyLm(decode_page_size=4)
    theta = task.InstantiateVariables(jax.random.PRNGKey(0))
    states = task.InitDecodeState(theta, 2, 15)
    logits, states = task.ExtendStep(
        theta, jnp.ones((2, 1), jnp.int32), states)
    assert logits.shape == (2, 64)
    assert np.all(np.isfinite(np.asarray(logits)))


class TestFlashDecodeKernel:

  def _Inputs(self, b=2, s=32, n=2, h=16):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, 1, n, h))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, n, h))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, n, h))
    pad = jnp.zeros((b, s)).at[0, :3].set(1.0)
    return q, k, v, pad

  @staticmethod
  def _DenseRef(q, k, v, t, pad):
    s_len = k.shape[1]
    s = jnp.einsum("BTNH,BSNH->BNTS", q, k).astype(jnp.float32)
    slot = jnp.arange(s_len)[None, None, None, :]
    mask = jnp.where(slot <= t, 0.0, -1e30) + pad[:, None, None, :] * -1e30
    p = jax.nn.softmax(jnp.maximum(s + mask, -1e30), -1)
    return jnp.einsum("BNTS,BSNH->BTNH", p, v)

  @pytest.mark.parametrize("t", [5, 8, 17, 31])
  def test_xla_twin_matches_dense_reference(self, t):
    q, k, v, pad = self._Inputs()
    out = flash_decode.FlashDecode(
        q, k, v, jnp.asarray(t, jnp.int32), page_size=8, cache_paddings=pad,
        lowering="xla")
    ref = self._DenseRef(q, k, v, t, pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)

  def test_xla_twin_full_cache_boundary(self):
    # out-of-contract t >= S must not re-read the clamped last page: the
    # live-page count is clamped to num_pages, so the answer equals dense
    # attention over every slot (what the Pallas grid computes).
    q, k, v, pad = self._Inputs()
    s = k.shape[1]
    for t in [s, s + 5]:
      out = flash_decode.FlashDecode(
          q, k, v, jnp.asarray(t, jnp.int32), page_size=8,
          cache_paddings=pad, lowering="xla")
      ref = self._DenseRef(q, k, v, t, pad)
      np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)

  def test_xla_twin_jits_with_dynamic_time_step(self):
    q, k, v, _ = self._Inputs()
    f = jax.jit(lambda t: flash_decode.FlashDecode(
        q, k, v, t, page_size=8, lowering="xla"))
    for t in [0, 9, 31]:
      out = f(jnp.asarray(t, jnp.int32))
      ref = self._DenseRef(q, k, v, t, jnp.zeros(k.shape[:2]))
      np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)

  @pytest.mark.slow
  def test_pallas_interpret_bitwise_equals_xla_twin(self):
    # one tiny shape: interpret mode costs ~8-10 ms per grid step on CPU
    q, k, v, pad = self._Inputs(b=1, s=16, n=1, h=8)
    for t in [0, 7, 8, 15]:
      ts = jnp.asarray(t, jnp.int32)
      out_x = flash_decode.FlashDecode(
          q, k, v, ts, page_size=8, cache_paddings=pad, lowering="xla")
      out_p = flash_decode.FlashDecode(
          q, k, v, ts, page_size=8, cache_paddings=pad, lowering="pallas",
          interpret=True)
      np.testing.assert_array_equal(np.asarray(out_x), np.asarray(out_p))


class TestDecodeBucketing:

  def test_round_up_to_bucket(self):
    buckets = (16, 32, 64)
    assert py_utils.RoundUpToBucket(1, buckets) == 16
    assert py_utils.RoundUpToBucket(16, buckets) == 16
    assert py_utils.RoundUpToBucket(17, buckets) == 32
    assert py_utils.RoundUpToBucket(64, buckets) == 64
    assert py_utils.RoundUpToBucket(65, buckets) == 65  # beyond: exact size
    with pytest.raises(ValueError):
      py_utils.RoundUpToBucket(-1, buckets)

  def test_ragged_prompt_widths_share_one_program(self, tmp_path):
    """Prompt widths 4 and 7 both bucket to 16: one compiled decode fn,
    continuations identical to exact-width programs."""
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

    driver = gshard_decode.GShardDecode(
        task, train_dir, str(tmp_path / "a.jsonl"), max_decode_steps=4)
    r1 = driver.DecodeOnce(1, np.array([[5, 6, 7, 8]], np.int32),
                           np.array([4], np.int32))
    r2 = driver.DecodeOnce(1, np.array([[5, 6, 7, 8, 9, 10, 11]], np.int32),
                           np.array([7], np.int32))
    assert len(driver._decode_fns) == 1, driver._decode_fns.keys()

    exact = gshard_decode.GShardDecode(
        task, train_dir, str(tmp_path / "b.jsonl"), max_decode_steps=4,
        len_buckets=(4, 7))
    e1 = exact.DecodeOnce(1, np.array([[5, 6, 7, 8]], np.int32),
                          np.array([4], np.int32))
    e2 = exact.DecodeOnce(1, np.array([[5, 6, 7, 8, 9, 10, 11]], np.int32),
                          np.array([7], np.int32))
    assert len(exact._decode_fns) == 2
    assert r1[0]["output_ids"] == e1[0]["output_ids"]
    assert r2[0]["output_ids"] == e2[0]["output_ids"]

  def test_legacy_prime_flag_matches_fast_path(self, tmp_path):
    """use_legacy_prime=True (the old per-token scan) emits the same
    greedy continuations as chunked prefill."""
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

    fast = gshard_decode.GShardDecode(
        task, train_dir, str(tmp_path / "f.jsonl"), max_decode_steps=4)
    legacy = gshard_decode.GShardDecode(
        task, train_dir, str(tmp_path / "l.jsonl"), max_decode_steps=4,
        use_legacy_prime=True)
    rf = fast.DecodeOnce(1, prompts, lens)
    rl = legacy.DecodeOnce(1, prompts, lens)
    for a, b in zip(rf, rl):
      assert a["output_ids"] == b["output_ids"]
