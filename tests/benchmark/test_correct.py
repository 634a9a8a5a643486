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


# -- a whole run with the timed path broken underneath ------------------------


def _StateUnchanged(monkeypatch):
  """The step returns the paged cache as it got it: nothing a request
  wrote is there when its next token reads it."""
  from benchmarks.harness import model as model_lib
  inner = model_lib.Instantiate

  def _Instantiate(task_p):
    task = inner(task_p)
    step = task.RaggedStep

    def _RaggedStep(theta, ids, states, *rest, **kw):
      logits, _ = step(theta, ids, states, *rest, **kw)
      return logits, states

    task.RaggedStep = _RaggedStep
    return task

  monkeypatch.setattr(model_lib, "Instantiate", _Instantiate)


def _LossyWeights(monkeypatch):
  """The engine serves its weights a tenth off, the reference keeps what the
  seed made. (The control proper, weights rounded to fp8 e4m3, averages out
  under the limit at the rehearsal's width of 64; at the cell's own width it
  reads 0.41 against 0.2, see the configuration's `serve_reason`.)"""
  import jax
  import jax.numpy as jnp
  from lingvo_tpu.serving import engine as engine_lib
  inner = engine_lib.ServingLoop.__init__

  def _Init(self, task, theta, *args, **kw):
    theta = jax.tree_util.tree_map(
        lambda x: (x * 0.9).astype(x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, theta)
    inner(self, task, theta, *args, **kw)

  monkeypatch.setattr(engine_lib.ServingLoop, "__init__", _Init)


@pytest.mark.parametrize("cell,fault,want", [
    ("dense1b_serve_docs", None, True),
    ("dense1b_serve_docs", _StateUnchanged, False),
    ("dense1b_serve_docs", _LossyWeights, False),
])
def test_a_run_with_the_timed_path_broken_is_not_correct(
    cell, fault, want, monkeypatch, tmp_path, capsys):
  """The rest of a run as run.py drives it (--rehearse takes the CPU for
  the chip), with the engine's step broken underneath, or with the control
  in the program's place: `correct` comes out false, the number compared
  stands beside its limit, and the sound run of the same cell passes."""
  import argparse
  from benchmarks import run as run_mod
  from benchmarks.harness import device
  monkeypatch.setattr(device, "ConfigureCache", lambda: "the tests' own")
  if fault is not None:
    fault(monkeypatch)
  args = argparse.Namespace(
      workload=cell, seed=3100000077, seconds=1.0, trace=0, rehearse=True,
      out=str(tmp_path), traffic_override="")
  assert run_mod._Run(args) == 0
  import json
  line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
  assert line["correct"] is want
  compared = line["compared"]["logit_max_abs_diff"]
  assert (compared["value"] <= compared["limit"]) is want
  assert line["failed"] == 0 and line["attempted"] > 0


def test_the_control_tool_runs_a_cell_with_fp8_weights_underneath(tmp_path):
  """benchmarks/tools/control.py, the control as it is run on the chip, here
  on the CPU at the rehearsal's width. There fp8's rounding reads 0.18-0.29
  against the limit of 0.2 (it averages out over a width of 64; at the
  cell's own width it reads twice the limit and more, PERF.md section 4), so
  this holds it to reading several times what a sound run reads (0.01-0.02),
  and its exit code to saying which side of the limit it fell on."""
  import json
  import subprocess
  import sys
  root = os.path.dirname(os.path.dirname(os.path.dirname(
      os.path.abspath(__file__))))
  env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
  env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
  done = subprocess.run(
      [sys.executable, os.path.join(root, "benchmarks", "tools", "control.py"),
       "--workload", "dense1b_serve_docs", "--seed", "3100000901",
       "--seconds", "1", "--rehearse", "--out", str(tmp_path)],
      cwd=root, env=env, capture_output=True, text=True, timeout=600)
  line = json.loads(done.stdout.strip().splitlines()[-1])
  assert line["control"] == "fp8_e4m3_weights" and line["failed"] == 0
  compared = line["compared"]["logit_max_abs_diff"]
  assert compared["value"] > 0.1, "five times a sound run's reading and more"
  assert line["correct"] is (compared["value"] <= compared["limit"])
  assert done.returncode == (1 if line["correct"] else 0)


# -- a run whose file says other sizes than the program has -------------------


def _FileSays(monkeypatch, **over):
  """The cell's configuration with `over` laid over its rehearsal sizes."""
  inner = spec.Cell

  def _Cell(bench, name, root=None):
    cell = inner(bench, name, root)
    cell["config"] = dict(cell["config"], rehearsal=dict(
        cell["config"]["rehearsal"], **over))
    return cell

  monkeypatch.setattr(spec, "Cell", _Cell)


def _ProgramIgnoresItsHeadSizeKey(monkeypatch):
  """A program that has a `dim_per_head` key and goes on deriving
  model_dim / heads: the file's head size is written, passes the file's own
  arithmetic, and is not what runs."""
  from lingvo_tpu import model_registry
  real = model_registry.GetParams

  def _Get(name, dataset):
    mp = real(name, dataset)
    mp.task.Define("dim_per_head", 0, "Head size (ignored).")
    return mp

  monkeypatch.setattr(model_registry, "GetParams", _Get)
  _FileSays(monkeypatch, dim_per_head=32, task_params={"dim_per_head": 32})


@pytest.mark.parametrize("cell,fault,says", [
    ("dense1b_serve_docs",
     lambda mp: _FileSays(mp, num_kv_heads=2),
     r"pool leaf body/self_atten/key has shape \(2, 34, 8, 4, 16\); the "
     r"configuration file states \(2, 16\)"),
    ("dense1b_serve_docs", _ProgramIgnoresItsHeadSizeKey,
     r"pool leaf .* the configuration file states \(4, 32\)"),
    ("dense1b_train_packed", _ProgramIgnoresItsHeadSizeKey,
     r"query projection stack/body/self_atten/atten/w_query has shape "
     r"\(2, 64, 4, 16\); the configuration file states \(4, 32\)"),
])
def test_a_run_whose_file_misstates_the_programs_sizes_fails(
    cell, fault, says, monkeypatch, tmp_path):
  """The roofline readers count with the file's heads, KV heads and head
  size. A run in which the program's own pool or query projection has
  others gives no result line: it fails and says which leaf."""
  import argparse
  from benchmarks import run as run_mod
  from benchmarks.harness import device
  monkeypatch.setattr(device, "ConfigureCache", lambda: "the tests' own")
  fault(monkeypatch)
  args = argparse.Namespace(
      workload=cell, seed=3400000031, seconds=1.0, trace=0, rehearse=True,
      out=str(tmp_path), traffic_override="")
  with pytest.raises(ValueError, match=says):
    run_mod._Run(args)
