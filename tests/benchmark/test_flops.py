"""The benchmark's own FLOP functions, against XLA's count of the program's
train step (core/computation_cost.py) and against hand arithmetic."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import flops
from benchmarks.harness import peaks

CFG = {"model_dim": 256, "num_heads": 4, "dim_per_head": 64,
       "hidden_dim": 1024, "vocab_size": 1024, "seq_len": 128,
       "num_layers": 2}


def test_full_attention_form_matches_xla_within_10_percent():
  """At causal=False the function counts what an unfused forward+backward
  computes, which is what XLA counts (it knows nothing of the mask)."""
  from lingvo_tpu import model_registry
  import lingvo_tpu.models.all_params  # noqa: F401
  from lingvo_tpu.core import computation_cost, input_policy
  mp = model_registry.GetParams("lm.synthetic_packed_input.DenseLmTiny",
                                "Train")
  mp.task.input = mp.input
  mp.task.Set(model_dim=256, num_layers=2, num_heads=4, hidden_dim=1024,
              vocab_size=1024, use_repeat_layer=False, remat_policy="none")
  mp.task.input.Set(vocab_size=1024, seq_len=128, batch_size=2)
  task = mp.task.Instantiate()
  task.FinalizePaths()
  state = task.CreateTrainState(jax.random.PRNGKey(0))
  batch = input_policy.Instantiate(
      mp.input).GetPreprocessedInputBatch().Transform(jnp.asarray)
  xla = float(computation_cost.TrainStepCost(task, state, batch)["flops"])
  mine = flops.TrainFlopsPerToken(CFG, causal=False) * 2 * 128
  assert 0.9 <= xla / mine <= 1.1, (xla, mine)


def test_causal_counts_attention_at_half_and_nothing_else_changes():
  full = flops.TrainFlopsPerToken(CFG, causal=False)
  causal = flops.TrainFlopsPerToken(CFG, causal=True)
  atten = 12.0 * 128 * 4 * 64 * 2
  assert full - causal == pytest.approx(atten / 2)
  assert flops.AttentionFlopsPerToken(CFG) == pytest.approx(atten)
  matmul = 6.0 * 2 * (4 * 256 * 256 + 2 * 256 * 1024) + 6.0 * 1024 * 256
  assert causal == pytest.approx(matmul + atten / 2)


def test_depth_and_length_overrides():
  one = flops.TrainFlopsPerToken(CFG, num_layers=1)
  two = flops.TrainFlopsPerToken(CFG, num_layers=2)
  emb = 6.0 * 1024 * 256
  assert two - emb == pytest.approx(2 * (one - emb))
  assert flops.TrainFlopsPerToken(CFG, seq_len=256) > two


def test_dense1b_flops_per_token():
  """13 layers of DenseLm1B: 6 * (13 * 50.3M + 65.5M) + attention."""
  cfg = {"model_dim": 2048, "num_heads": 16, "dim_per_head": 128,
         "hidden_dim": 8192, "vocab_size": 32000, "seq_len": 1024,
         "num_layers": 24}
  got = flops.TrainFlopsPerToken(cfg, num_layers=13)
  per_layer = 4 * 2048 * 2048 + 2 * 2048 * 8192
  want = 6 * 13 * per_layer + 6 * 32000 * 2048 + 6 * 1024 * 2048 * 13
  assert got == pytest.approx(want)


def test_flash_step_cost():
  ops, nbytes = flops.FlashTrainStepCost(8, 1024, 16, 128, 13)
  assert ops == pytest.approx(3.5 * 4 * 8 * 1024 * 1024 * 16 * 128 * 0.5 * 13)
  assert nbytes == pytest.approx(12 * 8 * 1024 * 16 * 128 * 2 * 13)


def test_ragged_step_cost():
  # one decode row at context 100 and one prefill chunk of 4 ending at 10
  ops, nbytes = flops.RaggedAttendStepCost(
      [(1, 100), (4, 10), (0, 55)], 544, 16, 128, 24)
  per = 4.0 * 16 * 128
  assert ops == pytest.approx(24 * per * (100 + (7 + 8 + 9 + 10)))
  assert nbytes == pytest.approx(24 * (2 * 110 + 2 * 544) * 16 * 128 * 2)


def test_roofline_share_says_which_bound_and_does_not_clamp():
  peak = peaks.PeakOf("TPU v5 lite")
  share, bound = flops.RooflineShare(197e12, 1.0, 2.0, peak)
  assert (round(share, 6), bound) == (50.0, "compute")
  share, bound = flops.RooflineShare(1.0, 819e9, 0.5, peak)
  assert (round(share, 6), bound) == (200.0, "memory")   # over 100 must show


def test_peak_table():
  v5e = peaks.PeakOf("TPU v5 lite")
  assert (v5e.flops_bf16, v5e.hbm_bytes_s) == (197e12, 819e9)
  assert v5e.source
  with pytest.raises(KeyError, match="no published peak"):
    peaks.PeakOf("cpu")
