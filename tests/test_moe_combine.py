"""The expert layer's combine (core/moe.py, scope `moe_combine`): the rows the
grouped matmuls hand back, un-sorted by one gather in their own dtype with `k`
the major axis, masked, weighted and summed over `k` in f32. Held to a plain
f32 loop over tokens and their experts; the lowered step pins the mechanism.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lingvo_tpu.core import moe

T, D, F, E = 12, 16, 8, 12
FIRST, HELD = 3, 5            # the held run of the cases that hold a share


def _Layer(k, held, dtype=jnp.float32, d=D, **kw):
  p = moe.DroplessMoELayer.Params().Set(
      name="moe", input_dim=d, hidden_dim=F, num_experts=E,
      num_experts_per_token=k, fprop_dtype=dtype, **kw)
  if held == "run":
    p.Set(first_expert=FIRST, num_experts_held=HELD)
  layer = p.Instantiate()
  layer.FinalizePaths()
  return layer, layer.InstantiateVariables(jax.random.PRNGKey(3))


def _Inputs(case, dtype, t=T, d=D, seed=11):
  rng = np.random.RandomState(seed)
  x = jnp.asarray(rng.randn(t, d), dtype)
  logits = jnp.asarray(rng.randn(t, E), jnp.float32)
  valid = {"full": None,
           "nan_rows": None,
           "padding": jnp.arange(t) < t - 3,
           "padding_nan_rows": jnp.arange(t) < t - 3,
           "padding_nan_logits": jnp.arange(t) < t - 3,
           "none_live": jnp.zeros((t,), bool)}[case]
  if case == "padding_nan_logits":
    # a padding token's activations are whatever the pack's tail held: its
    # weights can be NaN, and the parent's mask after the product hid them
    logits = jnp.where(valid[:, None], logits, jnp.nan)
  return x, logits, valid


def _NanPastTheRuns(lhs, rhs, group_sizes):
  """A grouped matmul whose rows past the last run are NaN: what
  `GroupedMatmul`'s docstring allows a lowering."""
  out = jax.lax.ragged_dot(lhs, rhs, group_sizes)
  rows = jnp.arange(lhs.shape[0])[:, None]
  return jnp.where(rows < jnp.sum(group_sizes), out, jnp.nan)


def _Reference(layer, theta, x, logits, valid):
  """A token and an expert at a time, everything f32: the k largest logits,
  the softmax over them, and of those k the experts held here."""
  p = layer.p
  f32 = lambda v: jnp.asarray(v, jnp.float32)
  x = f32(x)
  top_logits, top_idx = jax.lax.top_k(logits, p.num_experts_per_token)
  weights = jax.nn.softmax(top_logits, axis=-1)
  rows, counts = [], np.zeros(layer.num_held, np.int32)
  for t in range(x.shape[0]):
    row = jnp.zeros_like(x[t])
    for j in range(p.num_experts_per_token):
      e = int(top_idx[t, j]) - p.first_expert
      if (valid is not None and not bool(valid[t])) or not (
          0 <= e < layer.num_held):
        continue
      counts[e] += 1
      h = jax.nn.relu(x[t] @ f32(theta.w_gate[e])) * (x[t] @ f32(theta.w_up[e]))
      row = row + weights[t, j] * (h @ f32(theta.w_down[e]))
    rows.append(row)
  return jnp.stack(rows), counts


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ["full", "padding", "none_live", "nan_rows",
                                  "padding_nan_rows", "padding_nan_logits"])
@pytest.mark.parametrize("held", ["all", "run"])
@pytest.mark.parametrize("k", [4, 6, 10])
def test_experts_are_the_loop_over_tokens_and_their_experts(
    k, held, case, dtype, monkeypatch):
  if "nan_rows" in case or case == "none_live":
    # nothing of a row past the last run may reach the output
    monkeypatch.setattr(moe, "GroupedMatmul", _NanPastTheRuns)
  layer, theta = _Layer(k, held, dtype)
  x, logits, valid = _Inputs(case, dtype)
  out, counts = jax.jit(layer._Experts)(theta, x, logits, valid)
  assert out.dtype == dtype and out.shape == x.shape
  # the reference reads the weights the layer computes with
  want, want_counts = _Reference(layer, layer.CastTheta(theta), x, logits,
                                 valid)
  np.testing.assert_array_equal(np.asarray(counts), want_counts)
  if case == "none_live":
    assert not np.asarray(out, np.float32).any()
  if valid is not None:      # a padding token's routed sum is 0 to the bit
    assert not np.asarray(out, np.float32)[~np.asarray(valid)].any()
  tol = 1e-6 if dtype == jnp.float32 else 3e-2   # bf16: the models' tests'
  np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                             rtol=tol, atol=tol)


@pytest.mark.parametrize("case", ["full", "padding_nan_rows"])
@pytest.mark.parametrize("held", ["all", "run"])
@pytest.mark.parametrize("k", [4, 6, 10])
def test_grad_of_a_scalar_is_the_references(k, held, case, monkeypatch):
  if "nan_rows" in case:
    monkeypatch.setattr(moe, "GroupedMatmul", _NanPastTheRuns)
  layer, theta = _Layer(k, held)
  x, logits, valid = _Inputs(case, jnp.float32)
  cot = jnp.asarray(np.random.RandomState(5).randn(T, D), jnp.float32)
  names = ("w_gate", "w_up", "w_down")

  def _Scalar(fn):
    def _Of(x, logits, mats):
      th = theta.Copy()
      for name, m in zip(names, mats):
        th[name] = m
      return jnp.sum(fn(th, x, logits)[0] * cot)
    return jax.grad(_Of, argnums=(0, 1, 2))(
        x, logits, tuple(theta[n] for n in names))

  got = jax.jit(lambda: _Scalar(
      lambda th, x, logits: layer._Experts(th, x, logits, valid)))()
  want = _Scalar(lambda th, x, logits: _Reference(layer, th, x, logits, valid))
  for g, w in zip(jax.tree_util.tree_leaves(got),
                  jax.tree_util.tree_leaves(want)):
    assert np.isfinite(np.asarray(g)).all()
    np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                               atol=1e-5)


# -- the mechanism, in the lowered step ----------------------------------------


def _Equations(jaxpr):
  """Every equation of a jaxpr in program order, those of the calls it makes
  (pjit, custom_jvp, ...) in place of the call."""
  for eqn in jaxpr.eqns:
    inner = list(jax.core.jaxprs_in_params(eqn.params))
    if not inner:
      yield eqn
    for sub in inner:
      yield from _Equations(sub)


def test_the_combine_gathers_bf16_rows_k_major_and_sums_over_axis_0():
  """Pins the mechanism, not the numbers: after the last grouped matmul ONE
  gather of D-wide rows, bf16 in and bf16 out, into [k, T, D]; the reduce
  that ends the combine runs over axis 0; no [T * k, D] array is reshaped to
  [T, k, D] (on a TPU that put k on the sublanes: a padded copy)."""
  t, k, d = 64, 10, 256
  layer, theta = _Layer(k, "all", jnp.bfloat16, d=d)
  x, logits, _ = _Inputs("full", jnp.bfloat16, t=t, d=d)
  valid = jnp.arange(t) < t - 5
  eqns = list(_Equations(jax.make_jaxpr(layer._Experts)(
      theta, x, logits, valid).jaxpr))
  names = [e.primitive.name for e in eqns]
  matmuls = [i for i, n in enumerate(names) if n.startswith("ragged_dot")]
  assert len(matmuls) == 3, names                 # gate, up, down
  after = eqns[matmuls[-1] + 1:]
  wide = [e for e in after if e.primitive.name == "gather"
          and e.invars[0].aval.shape[-1:] == (d,)]
  assert len(wide) == 1, [str(e) for e in wide]
  (gather,) = wide
  assert gather.invars[0].aval.dtype == jnp.bfloat16
  assert gather.invars[0].aval.shape == (t * k, d)
  assert gather.outvars[0].aval.dtype == jnp.bfloat16
  assert gather.outvars[0].aval.shape == (k, t, d)
  # no f32 value is gathered anywhere behind the matmuls
  assert not [e for e in after if e.primitive.name == "gather"
              and e.invars[0].aval.dtype == jnp.float32
              and e.invars[0].aval.ndim == 2 and e.invars[0].aval.shape[0] > t]
  # the mask is on the bf16 VALUE, before the convert: with the convert first
  # XLA for the TPU leaves the f32 [T * k, D] copy outside the sum's fusion
  # (PERF.md section 6, PR 60)
  masks = [e for e in after if e.primitive.name == "select_n"
           and e.outvars[0].aval.shape == (k, t, d)]
  assert [e.outvars[0].aval.dtype for e in masks] == [jnp.bfloat16]
  sums = [e for e in after if e.primitive.name == "reduce_sum"
          and e.outvars[0].aval.shape == (t, d)]
  assert sums and sums[-1].params["axes"] == (0,)
  assert sums[-1].invars[0].aval.shape == (k, t, d)
  assert sums[-1].invars[0].aval.dtype == jnp.float32    # the sum is f32's
  for e in eqns:
    if e.primitive.name == "reshape":
      assert not (e.invars[0].aval.shape == (t * k, d)
                  and e.outvars[0].aval.shape == (t, k, d)), str(e)


# -- the probe that decided this form (ROADMAP D22) ----------------------------


@pytest.mark.parametrize("shape", ["granite", "smallthinker", "nemotron",
                                   "mistral"])
def test_the_kernel_probe_holds_every_variant_to_the_form_replaced(shape,
                                                                   capsys):
  """tools/kernel_probe.py --case moe_combine at the CPU's rehearsal sizes:
  the form PR 60 replaced is the reference, the layer's form and its other
  inverse agree with it, and no NaN of a row past the runs comes through."""
  import importlib.util
  import json
  import os
  spec = importlib.util.spec_from_file_location("kernel_probe", os.path.join(
      os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools",
      "kernel_probe.py"))
  kernel_probe = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(kernel_probe)
  assert kernel_probe.main(["--case", "moe_combine", "--tiny", "--calls", "1",
                            "--shapes", shape]) == 0
  lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
           if l.startswith("{")]
  assert [l["variant"] for l in lines] == ["parent", "argsort", "scatter"]
  assert all(l["within_1e-5"] for l in lines[1:])
  assert all(l["finite"] and l["tiny"] and l["device"]["platform"] == "cpu"
             and l["k"] == kernel_probe.COMBINE_SHAPES[shape][0]
             and 0 < l["live_pairs"] < l["tokens"] * l["k"] for l in lines)
