"""What decides `correct` in a serving cell: numbers against numbers."""

import os

import numpy as np
import pytest

from benchmarks.harness import readings
from benchmarks.harness import spec

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _Logits(seed=0, rows=3, vocab=50):
  x = np.random.RandomState(seed).normal(size=(rows, vocab)).astype(np.float32)
  x[:, 7] = 24.5            # the echoed id towers over the rest
  return x


@pytest.mark.parametrize("fault,want", [
    (lambda x: x, True),
    (lambda x: x + 0.1, True),                       # bf16 rounding
    (lambda x: x + 0.4 * (np.arange(x.shape[1]) == 3), False),  # one logit off
    (lambda x: x * 0.97, False),                     # a lower precision
    (lambda x: np.where(np.arange(x.shape[1]) == 11, np.nan, x), False),
    (lambda x: x[:, :-1], False),
])
def test_compare_logits_fails_on_numbers_the_argmax_hides(fault, want):
  ref = _Logits()
  got = fault(ref.copy())
  if got.shape == ref.shape and not np.isnan(got).any():
    # in every case the argmax is the echoed id: a token check would pass
    assert (got.argmax(-1) == ref.argmax(-1)).all()
  ok, detail = readings.CompareLogits(got, ref, 0.25)
  assert ok is want
  assert detail["tolerance"] == 0.25
  assert "error" in detail or len(detail["max_abs_diff_by_position"]) == 3


def test_logits_at_is_the_full_forward_at_those_positions():
  import jax
  import jax.numpy as jnp
  from benchmarks.references import dense_lm
  rng = np.random.RandomState(3)
  d, n, h, f, v, layers = 16, 2, 8, 32, 40, 2

  def _W(*shape):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.2)

  theta = {
      "emb": {"emb": _W(v, d)},
      "final_ln": {"scale": _W(d), "bias": _W(d)},
      "stack": {"body": {
          "self_atten": {
              "ln": {"scale": _W(layers, d), "bias": _W(layers, d)},
              "atten": {
                  "w_query": _W(layers, d, n, h), "b_query": _W(layers, n, h),
                  "w_key": _W(layers, d, n, h), "b_key": _W(layers, n, h),
                  "w_value": _W(layers, d, n, h), "b_value": _W(layers, n, h),
                  "w_post": _W(layers, d, n, h), "b_post": _W(layers, d),
                  "per_dim_scale": {"per_dim_scale": _W(layers, h)}}},
          "fflayer": {
              "ln": {"scale": _W(layers, d), "bias": _W(layers, d)},
              "ffn_in": {"w": _W(layers, d, f), "b": _W(layers, f)},
              "ffn_out": {"w": _W(layers, f, d), "b": _W(layers, d)}}}}}
  ids = jnp.asarray(rng.randint(1, v, size=(3, 12)), jnp.int32)
  at = jnp.asarray([11, 4, 0], jnp.int32)
  full = dense_lm.Logits(theta, ids)
  np.testing.assert_allclose(
      dense_lm.LogitsAt(theta, ids, at), full[jnp.arange(3), at], rtol=1e-5,
      atol=1e-5)
  # what follows a position is out of its sight
  other = ids.at[1, 5:].set(1)
  np.testing.assert_allclose(
      dense_lm.LogitsAt(theta, other, at)[1], full[1, 4], rtol=1e-5, atol=1e-5)
  assert float(jnp.abs(dense_lm.LogitsAt(theta, other, at)[0]
                       - full[0, 11]).max()) == 0.0
  del jax


def test_a_reader_with_nothing_to_read_is_left_out_and_a_broken_one_fails(
    tmp_path):
  os.makedirs(tmp_path / "benchmarks" / "layer_metrics")
  readers = {
      "has": "def Read(run):\n  return run['x']\n",
      "nothing": "def Read(run):\n  return run['no_such_span']\n",
      "none": "def Read(run):\n  return None\n",
      "broken": "def Read(run):\n  return run['x'] / 0\n",
      "nested": "def Read(run):\n  return run['d']['typo']\n",
  }
  for name, body in readers.items():
    (tmp_path / "benchmarks" / "layer_metrics" / (name + ".py")).write_text(
        body)

  def _Cell(*names):
    return {"root": str(tmp_path),
            "per_layer": [{"name": n, "unit": "u"} for n in names]}

  run = {"x": 3, "d": {}}
  assert spec.ReadLayerMetrics(_Cell("has", "nothing", "none"), run) == {
      "has": {"value": 3.0, "unit": "u"}}
  with pytest.raises(ZeroDivisionError):
    spec.ReadLayerMetrics(_Cell("has", "broken"), run)
  with pytest.raises(KeyError):
    spec.ReadLayerMetrics(_Cell("nested"), run)


def test_seeded_weights_scale_the_attention_output_and_nothing_else():
  import jax.numpy as jnp
  from benchmarks.references import dense_lm
  theta = {"emb": {"emb": jnp.ones((4, 2), jnp.bfloat16)},
           "stack": {"body": {
               "self_atten": {"atten": {
                   "w_post": jnp.ones((2, 2, 1, 2), jnp.bfloat16),
                   "w_query": jnp.ones((2, 2, 1, 2), jnp.bfloat16)}},
               "fflayer": {"ffn_out": {
                   "w": jnp.ones((2, 4, 2), jnp.bfloat16)}}}}}
  out = dense_lm.SeededWeights(theta, attention_out_scale=60.0)
  at = out["stack"]["body"]["self_atten"]["atten"]
  assert at["w_post"].dtype == jnp.bfloat16
  assert at["w_post"].shape == (2, 2, 1, 2)
  assert float(at["w_post"].astype(jnp.float32).min()) == 60.0
  assert float(at["w_query"].astype(jnp.float32).max()) == 1.0
  assert float(out["stack"]["body"]["fflayer"]["ffn_out"]["w"].astype(
      jnp.float32).max()) == 1.0
  assert float(out["emb"]["emb"].astype(jnp.float32).max()) == 1.0
