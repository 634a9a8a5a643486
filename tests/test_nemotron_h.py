"""NVIDIA-Nemotron-3-Nano-30B-A3B's mechanisms at a size the CPU holds, against
the plain reference (benchmarks/references/nemotron_h.py) and against numpy:
the Mamba-2 scan on the packed axis, the sigmoid router with its selection
bias, relu^2 experts beside a shared one, a stack whose layers are one branch
alone, the whole model, and the tiny registered sibling served by ServingLoop
in chunks and decode steps through slot state and one layer's pages."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import nemotron_h as ref
from lingvo_tpu import model_registry
from lingvo_tpu.core import moe as moe_lib
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.models.lm import layers as lm_layers
from lingvo_tpu.ops import packed_ssd_scan
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import spec_decode

import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)

# the served f32 model against the f32 reference; the same weights rounded
# to bf16 read 1e-2 and more (test_bf16_weights_fail_the_tolerance)
_LOGIT_TOL = 2e-4
_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _Task(name="Nemotron3NanoTiny", depth=None, dtype=None, **task_params):
  mp = model_registry.GetParams("lm.nemotron_h." + name, "Train")
  tp = mp.task
  tp.input = mp.input
  if depth is not None:
    tp.num_layers = depth
  if dtype is not None:
    tp.fprop_dtype = dtype
  for key, value in task_params.items():
    tp.SetPath(key, value)
  task = tp.Instantiate()
  task.FinalizePaths()
  return task


def _Seeded(task, key=7, **weights):
  theta = task.InstantiateVariables(jax.random.PRNGKey(key))
  # a selection bias that is not zero, so that one that weighed would show
  return ref.SeededWeights(theta, router_bias_spread=0.5, **weights)


@pytest.fixture(scope="module")
def tiny():
  """{depth: (task, theta)}: 9 layers are the cell's cut (a block of two
  repeats and five single ones); 13 scan `MEMEM*` twice."""
  out = {}
  for depth in (9, 13):
    task = _Task(depth=depth)
    out[depth] = task, _Seeded(task)
  return out


# -- the stack as data ---------------------------------------------------------


@pytest.mark.parametrize("depth", [9, 13, 52])
def test_pattern_letters_and_their_blocks(depth):
  kinds = [lm_layers.PATTERN_KINDS[c] for c in _PATTERN[:depth]]
  got = lm_layers.KindBlocks(kinds)
  assert [k for ks, r in got for k in ks * r] == kinds
  if depth == 9:
    assert got == [(["mamba2", "experts"], 2), (["mamba2"], 1), (["gqa"], 1),
                   (["experts"], 1), (["mamba2"], 1), (["experts"], 1)]
  if depth == 52:
    assert got[0] == (["mamba2", "experts", "mamba2", "experts", "mamba2",
                       "gqa", "experts"], 5)


def test_published_model_counts_its_layers_and_parameters():
  """Shapes only, nothing allocated: 31.58B in all, 3.2B a token."""
  task = _Task("Nemotron3Nano30BA3B")
  shapes = jax.eval_shape(task.InstantiateVariables, jax.random.PRNGKey(0))
  count = lambda tree: sum(int(np.prod(x.shape))
                           for x in jax.tree_util.tree_leaves(tree))
  stack = task.stack
  assert stack.LayerKinds() == {"Mamba2Layer": 23, "DroplessMoELayer": 23,
                                "PooledAttention": 6}
  per_kind = {}
  for b, (layers, reps) in enumerate(zip(stack._bodies, stack._repeats)):
    for j, layer in enumerate(layers):
      kind = type(layer.mixer or layer.fflayer).__name__
      per_kind[kind] = count(shapes.stack[f"block_{b}"].x_layers[j]) // reps
  d = 2688
  # the experts' matrices are stored padded to 15 x 128 columns
  # (moe._StoredWidth): zeros that are a layout and no parameters
  assert shapes.stack.block_0.x_layers[1].fflayer.w_up.shape == (
      5, 128, d, 1920)
  per_kind["DroplessMoELayer"] -= 128 * 2 * d * (1920 - 1856)
  assert per_kind["DroplessMoELayer"] == (
      d * 128 + 128 + 128 * 2 * d * 1856 + 2 * d * 3712 + d)
  assert per_kind["Mamba2Layer"] == (
      d * 10304 + 4096 * d + 5 * 6144 + 3 * 64 + 4096 + d)
  assert per_kind["PooledAttention"] == 2 * d * 32 * 128 + 2 * d * 2 * 128 + d
  total = count(shapes) - 23 * 128 * 2 * d * (1920 - 1856)
  assert total == 23 * per_kind["DroplessMoELayer"] + 23 * per_kind[
      "Mamba2Layer"] + 6 * per_kind["PooledAttention"] + 2 * 131072 * d + d
  assert round(total / 1e9, 2) == 31.58                   # published 31.6B
  routed = 23 * 122 * 2 * d * 1856                 # experts a token skips
  assert round((total - routed) / 1e9, 1) == 3.6   # with embedding and head
  assert round((total - routed - 131072 * d) / 1e9, 1) == 3.2   # A3.2B
  census = kv_cache.StackCensus(task)
  assert census["num_ssm"] == 23 and census["num_attention"] == 6
  assert census["decode_state_bytes_per_slot"] == 23 * 4 * (
      64 * 64 * 128 + 3 * 6144)
  assert stack.PageWindows() == [0] * 6


# -- the Mamba-2 scan on the packed axis ---------------------------------------


def _NaiveScan(x, dt, a, b, c, d, state, row_len, q_pos):
  t, hm, p = x.shape
  r = hm // b.shape[1]
  y, s_out, cur = np.zeros((t, hm, p)), np.array(state), 0
  for i, n in enumerate(row_len):
    s = np.zeros_like(s_out[i]) if q_pos[i] == 0 else np.array(state[i])
    for j in range(n):
      k = cur + j
      bb, cc = np.repeat(b[k], r, 0), np.repeat(c[k], r, 0)      # [Hm, N]
      s = (np.exp(dt[k] * a)[:, None, None] * s
           + (dt[k][:, None] * x[k])[:, :, None] * bb[:, None, :])
      y[k] = (s * cc[:, None, :]).sum(-1) + d[:, None] * x[k]
    s_out[i], cur = s, cur + n
  return y, s_out


@pytest.mark.parametrize("lowering", ["xla", "pallas", "sequential"])
@pytest.mark.parametrize("row_len,q_pos", [
    ((1, 0, 16, 1, 7), (5, 9, 0, 0, 3)),          # decode rows beside chunks
    ((1, 1, 1, 1, 1), (4, 0, 9, 2, 7)),           # a decode-only step
    ((0, 0, 13, 0, 0), (3, 3, 40, 3, 3)),         # one chunk, not from zero
    ((3, 21, 0, 9, 5), (0, 7, 1, 0, 2)),          # rows across scan chunks
    ((0, 38, 0, 0, 0), (1, 0, 1, 1, 1)),          # one row over five chunks
    # the row pass's one-token body (PR 58): a decode token that is the LAST
    # of a scan chunk and one that is the FIRST; the only chunk row come in
    # from an earlier chunk beside decode rows; slots without a token
    ((7, 1, 1, 1, 1), (0, 3, 5, 0, 9)),
    ((1, 1, 20, 1, 1), (4, 2, 6, 0, 3)),
    ((0, 1, 0, 1, 0), (2, 5, 1, 0, 7)),
], ids=["mixed", "decode_only", "one_chunk", "across_chunks", "long_row",
        "decode_at_chunk_edges", "one_row_came_in", "idle_beside_decode"])
def test_ssd_scan_on_the_packed_axis(lowering, row_len, q_pos):
  rng = np.random.RandomState(0)
  slots, hm, p, g, n, t_, w_ = 5, 4, 64, 2, 128, 40, 38
  rows = ragged_lib.BuildRaggedRows(np.array(row_len), np.array(q_pos), t_, w_)
  rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows))
  f32 = lambda v: np.asarray(v, np.float32)
  dt = f32(rng.uniform(0.001, 0.5, (t_, hm)))
  x, b, c = (f32(rng.randn(t_, *k)) for k in ((hm, p), (g, n), (g, n)))
  a = -f32(rng.uniform(1, 16, hm))
  d, state = f32(rng.randn(hm)), f32(rng.randn(slots, hm, p, n))
  y, s = packed_ssd_scan.PackedSsdScan(
      *(jnp.asarray(v) for v in (x, dt, a, b, c, d, state)), rows,
      chunk_size=8, lowering=lowering)
  want_y, want_s = _NaiveScan(x, dt, a, b, c, d, state, row_len, q_pos)
  np.testing.assert_allclose(np.asarray(y), want_y, atol=3e-5)
  np.testing.assert_allclose(np.asarray(s), want_s, atol=3e-5)


# -- the expert layer's new Params ---------------------------------------------


def _MoE(**kw):
  p = moe_lib.DroplessMoELayer.Params().Set(
      name="moe", input_dim=24, hidden_dim=20, num_experts=8,
      num_experts_per_token=3, **kw)
  layer = p.Instantiate()
  layer.FinalizePaths()
  return layer, layer.InstantiateVariables(jax.random.PRNGKey(3))


def _NumpyExperts(theta, x, bias, k=3, scale=2.5, shared=True):
  """Section 1's `E` branch, a token and an expert at a time."""
  f = lambda v: np.asarray(v, np.float64)
  ms = (x ** 2).mean(-1, keepdims=True)
  u = x / np.sqrt(ms + 1e-6) * (1 + f(theta.ln.scale))
  s = 1 / (1 + np.exp(-(u @ f(theta.w_router))))
  out = np.zeros_like(x)
  chosen = []
  for t in range(x.shape[0]):
    top = np.argsort(-(s[t] + bias), kind="stable")[:k]
    chosen.append(sorted(top.tolist()))
    for e in top:
      h = np.maximum(u[t] @ f(theta.w_up[e]), 0) ** 2
      out[t] += scale * s[t, e] / s[t, top].sum() * (h @ f(theta.w_down[e]))
    if shared:
      h = np.maximum(u[t] @ f(theta.w_shared_up), 0) ** 2
      out[t] += h @ f(theta.w_shared_down)
  return x + out, chosen


_SIGMOID = dict(scoring="sigmoid", routed_scale=2.5, activation="relu2",
                shared_hidden_dim=12, router_reads="normed_input")


def test_sigmoid_router_relu2_experts_and_the_shared_one():
  """Scores sigmoid over all, the bias chooses and does not weigh, 2.5,
  renormalised, no gate matrix, the shared expert for every token once."""
  layer, theta = _MoE(**_SIGMOID)
  assert "w_gate" not in theta and "w_shared_gate" not in theta
  assert layer.StackAddressed() == ("w_up", "w_down")
  x = np.random.RandomState(1).randn(10, 24)
  bias = np.linspace(-0.6, 0.6, 8)[::-1].copy()
  theta.router_bias = jnp.asarray(bias, jnp.float32)
  got, counts = layer.FPropWithCounts(theta, jnp.asarray(x, jnp.float32))
  want, chosen = _NumpyExperts(theta, x, bias)
  np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
  assert counts.tolist() == [sum(e in c for c in chosen) for e in range(8)]
  # the bias changed what a zero bias would have chosen
  assert chosen != _NumpyExperts(theta, x, np.zeros(8))[1]
  # counted once: without it the output moves by exactly the shared expert
  theta_no = theta.Copy()
  theta_no.w_shared_down = jnp.zeros_like(theta.w_shared_down)
  routed, _ = layer.FPropWithCounts(theta_no, jnp.asarray(x, jnp.float32))
  want_routed, _ = _NumpyExperts(theta, x, bias, shared=False)
  np.testing.assert_allclose(np.asarray(routed), want_routed, atol=2e-5)


def test_a_bias_that_changes_no_choice_changes_nothing_to_the_bit():
  layer, theta = _MoE(**_SIGMOID)
  x = jnp.asarray(np.random.RandomState(2).randn(10, 24), jnp.float32)
  base = layer.FProp(theta, x)
  same = theta.Copy()
  same.router_bias = jnp.full((8,), 0.37, jnp.float32)   # moves every score
  assert np.array_equal(np.asarray(layer.FProp(same, x)), np.asarray(base))
  other = theta.Copy()
  other.router_bias = jnp.asarray(np.linspace(-1, 1, 8), jnp.float32)
  assert np.abs(np.asarray(layer.FProp(other, x)) - np.asarray(base)).max() > 1e-3


def test_defaults_are_the_layer_as_it_was_to_the_bit():
  """softmax over the chosen logits of the handed-in router, ReGLU, three
  matrices, no shared expert, no bias: the arithmetic the mixed cell's model
  has always run, written out beside the layer."""
  layer, theta = _MoE()
  assert sorted(theta.keys()) == ["ln", "w_down", "w_gate", "w_router", "w_up"]
  x = jnp.asarray(np.random.RandomState(4).randn(12, 24), jnp.float32)
  logits = layer.RouterLogits(theta, x)
  got, counts = layer.FPropWithCounts(theta, x, logits)
  u = layer.ln.FProp(theta.ln, x)
  top, idx = jax.lax.top_k(logits, 3)
  w = jax.nn.softmax(top, -1)
  flat = idx.reshape(-1)
  order = jnp.argsort(flat, stable=True)
  sizes = jnp.bincount(flat, length=9)[:8].astype(jnp.int32)
  xs = u[order // 3]
  h = jax.nn.relu(jax.lax.ragged_dot(xs, theta.w_gate, sizes))
  h = h * jax.lax.ragged_dot(xs, theta.w_up, sizes)
  ys = jax.lax.ragged_dot(h, theta.w_down, sizes) * w.reshape(-1)[order][:, None]
  want = x + ys[jnp.argsort(order)].reshape(12, 3, 24).sum(1)
  assert np.array_equal(np.asarray(got), np.asarray(want))
  assert counts.tolist() == sizes.tolist()


def test_the_padded_layout_is_the_unpadded_one_to_the_bit(monkeypatch):
  """Where the grouped-matmul kernel tiles the model dim and not the experts'
  width (`moe._StoredWidth`; a tile of 8 here: 24 is three, 20 is two and a
  half), the matrices are stored at the next whole tile: zero columns of W_up
  and zero rows of W_down behind the model's width, made where the matrices
  are made; the same weights give the same output to the bit (relu(0)^2 = 0).
  A model dim the kernel cannot tile pads nothing."""
  plain, theta = _MoE(**_SIGMOID)
  assert theta.w_up.shape == (8, 24, 20)          # 24 is no multiple of 128
  monkeypatch.setattr(moe_lib, "_GMM_TILE", 8)
  padded, theta_p = _MoE(**_SIGMOID)
  assert theta_p.w_up.shape == (8, 24, 24)
  assert theta_p.w_down.shape == (8, 24, 24)
  assert float(jnp.abs(theta_p.w_up[..., 20:]).max()) == 0.0
  assert float(jnp.abs(theta_p.w_down[:, 20:]).max()) == 0.0
  assert float(jnp.abs(theta_p.w_up[..., :20]).min()) > 0.0
  theta_p.w_up = theta_p.w_up.at[..., :20].set(theta.w_up)
  theta_p.w_down = theta_p.w_down.at[:, :20].set(theta.w_down)
  for name in ("w_router", "w_shared_up", "w_shared_down"):
    theta_p[name] = theta[name]
  x = jnp.asarray(np.random.RandomState(6).randn(10, 24), jnp.float32)
  assert np.array_equal(np.asarray(plain.FProp(theta, x)),
                        np.asarray(padded.FProp(theta_p, x)))


@pytest.mark.parametrize("name,model", [
    ("smallthinker", "lm.smallthinker.SmallThinkerTiny"),
    ("phi4flash", "lm.phi4flash.Phi4MiniFlashTiny")])
def test_the_other_cells_tiny_models_keep_their_variables_and_states(name,
                                                                     model):
  """No variable and no state leaf of the mixed cell's or the reason cell's
  model is new, gone or of another shape."""
  mp = model_registry.GetParams(model, "Train")
  tp = mp.task
  tp.input = mp.input
  task = tp.Instantiate()
  task.FinalizePaths()
  shapes = jax.eval_shape(task.InstantiateVariables, jax.random.PRNGKey(0))
  names = sorted({str(getattr(p[-1], "key", p[-1])) for p, _ in
                  jax.tree_util.tree_flatten_with_path(shapes)[0]})
  want = {
      "smallthinker": ["emb", "scale", "w_down", "w_gate", "w_key", "w_post",
                       "w_query", "w_router", "w_up", "w_value"],
      "phi4flash": ["a_log", "b_dt", "bias", "conv_b", "conv_w", "d_skip",
                    "emb", "lambda_k1", "lambda_k2", "lambda_q1", "lambda_q2",
                    "scale", "subln_scale", "w", "w_1", "w_2", "w_dt", "w_in",
                    "w_key", "w_out", "w_post", "w_query", "w_value",
                    "w_x"]}[name]
  assert [n for n in names if n not in want] == []
  assert [n for n in want if n not in names] == []
  states = jax.eval_shape(
      lambda th: task.InitPagedDecodeState(th, 9, 8, 2), shapes)
  leaves = sorted({str(getattr(p[-1], "key", p[-1])) for p, _ in
                   jax.tree_util.tree_flatten_with_path(states)[0]})
  assert leaves == {"smallthinker": ["key", "routed", "value"],
                    "phi4flash": ["conv", "key", "scan", "value"]}[name]


@pytest.mark.parametrize("width", [20, 1856 // 16])
def test_grouped_matmul_at_a_width_that_is_no_multiple_of_128(width):
  rng = np.random.RandomState(0)
  sizes = np.array([3, 0, 5, 1], np.int32)
  lhs = rng.randn(16, 24).astype(np.float32)
  rhs = rng.randn(4, 24, width).astype(np.float32)
  got = np.asarray(moe_lib.GroupedMatmul(
      jnp.asarray(lhs), jnp.asarray(rhs), jnp.asarray(sizes)))
  at = 0
  for g, n in enumerate(sizes):
    np.testing.assert_allclose(got[at:at + n], lhs[at:at + n] @ rhs[g],
                               atol=1e-5)
    at += n


# -- the whole model ------------------------------------------------------------


def _ReferenceLogits(theta, seq, at, width=128):
  ids = np.zeros((1, width), np.int32)
  ids[0, :len(seq)] = seq
  return np.asarray(jax.jit(lambda th, i, a: ref.LogitsAt(th, i, a, 0.0))(
      theta, jnp.asarray(ids), jnp.asarray([at], jnp.int32)))[0]


@pytest.mark.parametrize("depth", [9, 13])
def test_whole_model_forward_is_the_references(tiny, depth):
  task, theta = tiny[depth]
  ids = np.random.RandomState(4).randint(1, 128, (2, 64)).astype(np.int32)
  logits = task.ComputePredictions(theta, NestedMap(
      ids=jnp.asarray(ids), paddings=jnp.zeros((2, 64)))).logits
  for row, at in ((0, 63), (1, 30), (1, 2)):
    want = _ReferenceLogits(theta, ids[row], at)
    np.testing.assert_allclose(np.asarray(logits[row, at]), want,
                               atol=_LOGIT_TOL)


# -- the tiny sibling through ServingLoop --------------------------------------


class _Probe:
  """Every step through the task's ragged step with its logits kept:
  {(slot, position): logits [V]} of every valid token, and every step's
  (row_len, row_q_pos)."""

  def __init__(self, engine, task):
    self.engine, self.seen, self.steps = engine, {}, []
    self._fn = jax.jit(lambda th, st, ids, rows, tables: task.RaggedStep(
        th, ids[None], st, tables, rows))
    self._inner = engine._compile_log.Call
    engine._compile_log.Call = self._Call

  def _Call(self, name, fn, *args):
    if name != "ragged":
      return self._inner(name, fn, *args)
    theta, states, tok_ids, rows, tables = args[:5]
    logits, new_states = self._fn(theta, states, tok_ids, rows, tables)
    logits = np.asarray(logits[0].astype(jnp.float32))
    for col in np.flatnonzero(np.asarray(rows.valid)):
      key = int(np.asarray(rows.row_of)[col]), int(np.asarray(rows.pos)[col])
      self.seen[key] = logits[col]
    self.steps.append((np.asarray(rows.row_len).tolist(),
                       np.asarray(rows.row_q_pos).tolist()))
    routed = engine_lib._MoeCountLeaves(new_states)
    return (jnp.asarray(logits.argmax(-1), jnp.int32),
            jnp.concatenate(routed, axis=0), new_states)


def _Engine(task, theta, slots, **kw):
  return engine_lib.ServingLoop(task, theta, page_size=8, num_pages=48,
                                max_batch=slots, max_seq_len=128,
                                prefill_token_budget=16, **kw)


def _Serve(task, theta, prompts, new_tokens, between=None, slots=None):
  eng = _Engine(task, theta, slots or len(prompts))
  probe = _Probe(eng, task)
  handles = [eng.Submit(p, new_tokens) for p in prompts]
  for step in range(600):
    if all(h.done for h in handles):
      break
    eng.StepOnce()
    if between is not None:
      between(eng, step)
  assert all(h.done for h in handles)
  return eng, probe, [h.Result() for h in handles]


# 19: the second chunk is the convolution's K - 1 = 3 tokens, and the chunk
# boundary at 16 is a scan chunk's too; 21 and 50 beside 10: rows whose
# chunks start and end inside a scan chunk of 8
_PROMPTS = {"tail_of_three": [19], "shorter_than_a_chunk": [10],
            "uneven_chunks_in_one_step": [90, 10, 50],
            "boundary_inside_a_scan_chunk": [21, 50]}


def _Prompts(case):
  rng = np.random.RandomState(5)
  return [rng.randint(1, 128, n).astype(np.int32) for n in _PROMPTS[case]]


def _HoldToReference(theta, prompts, outs, seen, tol):
  for slot, (prompt, out) in enumerate(zip(prompts, outs)):
    seq = np.concatenate([prompt, np.asarray(out, np.int32)])
    for at in (len(prompt) - 1, len(seq) - 2):
      want = _ReferenceLogits(theta, seq, at)
      np.testing.assert_allclose(seen[slot, at], want, atol=tol,
                                 err_msg=f"row {slot} position {at}")


@pytest.fixture(scope="module")
def served(tiny):
  """{(depth, case): (engine, probe, streamed tokens)}, served once."""
  cache = {}

  def _Get(depth, case):
    if (depth, case) not in cache:
      task, theta = tiny[depth]
      cache[depth, case] = _Serve(task, theta, _Prompts(case), 8)
    return cache[depth, case]

  return _Get


@pytest.mark.parametrize("depth,case", [(9, c) for c in _PROMPTS] + [
    (13, "uneven_chunks_in_one_step")])
def test_chunked_prefill_and_decode_match_the_reference(tiny, served, depth,
                                                        case):
  """Prefill in chunks (a budget of 16 shared by the rows of a step, so
  uneven ones) and 8 decode steps through slot state and one layer's pages:
  the step's logits at the end of the prompt and at the last token fed back
  equal the reference's full forward there."""
  _, theta = tiny[depth]
  eng, probe, outs = served(depth, case)
  _HoldToReference(theta, _Prompts(case), outs, probe.seen, _LOGIT_TOL)
  stats = eng.Stats()
  assert stats["kv_pages"]["in_use"] == 0
  assert stats["state_slots"]["in_use"] == 0


def test_rows_that_start_continue_and_decode_in_one_packed_step(tiny):
  """A decoding row, a row in the middle of its prompt and a row at its
  first token in ONE step, each held to the reference afterwards."""
  task, theta = tiny[9]
  rng = np.random.RandomState(9)
  prompts = [rng.randint(1, 128, n).astype(np.int32) for n in (6, 44, 12)]
  eng = _Engine(task, theta, 3)
  probe = _Probe(eng, task)
  handles = [eng.Submit(prompts[0], 12)]
  eng.StepOnce()                                   # row 0's prompt, whole
  handles.append(eng.Submit(prompts[1], 6))
  eng.StepOnce()                                   # row 1's first chunk
  handles.append(eng.Submit(prompts[2], 6))
  while not all(h.done for h in handles):
    eng.StepOnce()
  kinds = [{"decode" if q > 0 and n == 1 else
            "start" if q == 0 else "continue"
            for n, q in zip(*step) if n > 0} for step in probe.steps]
  assert {"decode", "start", "continue"} in kinds, kinds
  _HoldToReference(theta, prompts, [h.Result() for h in handles], probe.seen,
                   _LOGIT_TOL)


def test_bf16_weights_fail_the_tolerance(tiny):
  task, theta = tiny[9]
  rounded = jax.tree_util.tree_map(
      lambda x: x.astype(jnp.bfloat16).astype(x.dtype), theta)
  prompt = _Prompts("shorter_than_a_chunk")[0]
  _, probe, outs = _Serve(task, rounded, [prompt], 2)
  seq = np.concatenate([prompt, np.asarray(outs[0], np.int32)])
  diff = np.abs(probe.seen[0, 9] - _ReferenceLogits(theta, seq, 9)).max()
  assert diff > 10 * _LOGIT_TOL, diff


def test_bf16_serving_stays_inside_its_tolerance(tiny):
  """The program as the benchmark runs it (bf16 weights and activations, f32
  scan state, the router in f32 over dimensions no layer writes) against the
  f32 reference of the same bf16 weights."""
  task = _Task(depth=9, dtype=jnp.bfloat16)
  theta = jax.tree_util.tree_map(
      lambda x: x.astype(jnp.bfloat16), _Seeded(task, router_reads_share=0.25,
                                                router_scale=8.0))
  prompts = _Prompts("uneven_chunks_in_one_step")
  _, probe, outs = _Serve(task, theta, prompts, 8)
  _HoldToReference(theta, prompts, outs, probe.seen, 0.12)


def test_a_reused_slot_starts_from_zero_state(tiny):
  """Two requests through the one slot, one after the other: the second
  reads nothing the first left in the scan state or the convolution tail."""
  task, theta = tiny[9]
  a, b = _Prompts("uneven_chunks_in_one_step")[1:]
  eng = _Engine(task, theta, 1)
  probe = _Probe(eng, task)
  first = eng.Submit(a, 4)
  while not first.done:
    eng.StepOnce()
  assert float(jnp.abs(eng._states.blocks[0][0].scan).max()) > 0
  assert float(jnp.abs(eng._states.blocks[0][0].conv).max()) > 0
  probe.seen.clear()
  second = eng.Submit(b, 4)
  while not second.done:
    eng.StepOnce()
  _HoldToReference(theta, [b], [second.Result()], probe.seen, _LOGIT_TOL)


@pytest.mark.parametrize("leaf", ["conv", "scan"])
def test_slot_state_carries_a_prompt_across_its_chunks(tiny, leaf):
  """The control to the chunked-prefill test: with the convolution tail (or
  the scan state) dropped before the prompt's last chunk, its last token
  reads far off the reference."""
  task, theta = tiny[9]

  def _Drop(eng, step):
    if step == 4:                                 # 80 of 90 tokens are in
      for block in eng._states.blocks:
        for layer in block:
          if leaf in layer:
            layer[leaf] = jnp.zeros_like(layer[leaf])

  prompts = [_Prompts("uneven_chunks_in_one_step")[0]]
  _, probe, outs = _Serve(task, theta, prompts, 2, between=_Drop)
  seq = np.concatenate([prompts[0], np.asarray(outs[0], np.int32)])
  diff = np.abs(probe.seen[0, 89] - _ReferenceLogits(theta, seq, 89)).max()
  assert diff > 20 * _LOGIT_TOL, diff


def test_slot_state_survives_a_spill_and_a_restore(tiny):
  """Mid-prompt, the slot's state rows go to the host (the engine's slot
  gather), the device's are overwritten, and come back (the engine's slot
  scatter): the stream is unchanged."""
  task, theta = tiny[9]

  def _SpillRestore(eng, step):
    if step == 2:
      rows = eng._SpillStateRow(0)
      # scan [Hm, P, N] and tail [K - 1, C] of four Mamba-2 layers
      assert sorted(r.shape for r in rows) == sorted(
          [(2, 8, 8, 16), (2, 3, 128)] + [(1, 8, 8, 16), (1, 3, 128)] * 2)
      for block in eng._states.blocks:
        for layer in block:
          for name in ("scan", "conv"):
            if name in layer:
              layer[name] = jnp.full_like(layer[name], 7.0)
      eng._RestoreStateRow(0, rows)

  prompts = [_Prompts("uneven_chunks_in_one_step")[0]]
  _, probe, outs = _Serve(task, theta, prompts, 4, between=_SpillRestore)
  _HoldToReference(theta, prompts, outs, probe.seen, _LOGIT_TOL)


def test_one_pool_one_table_slot_state_and_both_counter_groups(tiny, served):
  """The engine keeps a state pool AND expert counts on this stack, and says
  how many layers of each kind it holds."""
  task, theta = tiny[9]
  eng, _, outs = served(9, "uneven_chunks_in_one_step")
  st = eng.Stats()
  assert st["layer_kinds"] == {"Mamba2Layer": 4, "DroplessMoELayer": 4,
                               "PooledAttention": 1}
  assert eng._kind_pages.windows == (0,) and eng.alloc.num_pages == 48
  pools = [tuple(x.shape) for x in jax.tree_util.tree_leaves(eng._states)
           if x.ndim == 4 and x.shape[1] == 8 and x.shape[0] == 49]
  assert pools == [(49, 8, 2, 8)] * 2                     # K and V, once
  assert eng._states.blocks[0][0].scan.shape == (2, 3, 8, 8, 16)
  assert eng._states.blocks[0][1].routed.shape == (2, 8)
  assert eng.mixers == {"num_attention": 1, "num_ssm": 4,
                        "decode_state_bytes_per_slot": 4 * 4 * (
                            8 * 8 * 16 + 3 * 128)}
  prompt_tokens = sum(_PROMPTS["uneven_chunks_in_one_step"])
  streamed = sum(len(o) for o in outs)
  assert st["ssm_tokens"] == prompt_tokens + streamed - 3
  # every token reaches three experts in each of four layers
  assert st["moe_tokens_routed"] == 4 * 3 * st["ssm_tokens"]
  assert 0 < st["moe_experts_active"] <= 4 * 8 * st["steps"]
  records = [r for r in eng.trace.Steps() if r.counters]
  assert {"ssm_tokens", "moe_tokens_routed"} <= set(records[-1].counters)


@pytest.mark.parametrize("kw,names", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec=spec_decode.SelfDraft(k=2, num_layers=2)), "spec"),
])
def test_paths_of_one_block_table_refuse_the_stack(tiny, kw, names):
  task, theta = tiny[9]
  with pytest.raises(ValueError, match=names):
    _Engine(task, theta, 2, **kw)


def test_an_expert_layer_runs_no_mixer_and_a_mixer_layer_no_feed_forward(tiny):
  """What a layer's step holds, by its variables and by the device scopes of
  its operations: an `E` layer has no mixer, no mixer's norm and no op under
  `atten`; an `M` and a `*` layer no feed-forward and no op under `ffn`."""
  import re
  task, theta = tiny[9]
  states = task.InitPagedDecodeState(theta, 9, 8, 2)
  rows = ragged_lib.BuildRaggedRows(np.array([3, 1]), np.array([0, 4]), 8, 4)
  rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows))
  shared = NestedMap(kv_pool=states.kv_pool)
  x = jnp.zeros((1, 8, 48), jnp.float32)
  for (b, j), (has, lacks, absent) in {
      (0, 0): ({"atten", "ln"}, {"fflayer"}, "ffn"),        # M
      (0, 1): ({"fflayer"}, {"atten", "ln"}, "atten"),      # E
      (2, 0): ({"atten", "ln"}, {"fflayer"}, "ffn")}.items():  # *
    layer = task.stack._bodies[b][j]
    th = jax.tree_util.tree_map(lambda v: v[0],
                                theta.stack[f"block_{b}"].x_layers[j])
    st = jax.tree_util.tree_map(lambda v: v[0], states.blocks[b][j])
    assert has <= set(th.keys()) and not lacks & set(th.keys())
    text = jax.jit(lambda th, x, st, shared: layer.RaggedStep(
        th, x, st, shared, rows, jnp.zeros((2, 4), jnp.int32), 0, None)
                   ).lower(th, x, st, shared).as_text(debug_info=True)
    scopes = set(re.findall(r"[/\"](atten|ffn)[/\"]", text))
    assert scopes == {"atten", "ffn"} - {absent}, ((b, j), scopes)


# -- a layer that holds a share of the experts its router scores ---------------


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_of_the_tiny_presets_experts_add_up_to_the_reference(
    tiny, shares, monkeypatch):
  """Nemotron3NanoTiny's expert layer (8 relu2 experts top-3, sigmoid scores
  over all, a selection bias, a shared expert, the router on the layer's own
  normed input) cut into `shares` contiguous runs: the routed parts of all the
  shares plus the shared expert ONCE are the uncut layer's output as the
  reference computes it. Every share computes the shared expert; its weights
  stay the chosen scores over the sum of all three wherever they live."""
  task, theta = tiny[9]
  block = next(i for i, (layers, _) in enumerate(task.stack.p.blocks)
               if any(l.tr_fflayer_tpl is not None and l.mixer_tpl is None
                      for l in layers))
  body = getattr(task.stack, f"block_{block}")
  j = next(i for i, l in enumerate(body.x_layers) if l.mixer is None)
  tpl = body.x_layers[j].fflayer.p.Copy()
  th = jax.tree_util.tree_map(
      lambda a: a[0], theta.stack[f"block_{block}"].x_layers[j].fflayer)
  e, d = tpl.num_experts, tpl.input_dim
  monkeypatch.setattr(ref, "_PIECE", 4)
  ref._ARCH.update(ref._Arch(d))
  x = jnp.asarray(np.random.RandomState(shares).randn(19, d), jnp.float32)
  layer_ff = {"fflayer": jax.tree_util.tree_map(lambda a: a[None], dict(th))}
  want = ref._Experts(layer_ff, 0, x, 1)
  u = ref._RmsNorm(x, th.ln.scale)
  shared = jnp.square(jax.nn.relu(u @ th.w_shared_up)) @ th.w_shared_down
  held = e // shares
  total, counted = jnp.zeros_like(x), 0
  for s in range(shares):
    layer = tpl.Copy().Set(name="moe", first_expert=s * held,
                           num_experts_held=held).Instantiate()
    layer.FinalizePaths()
    mine = th.Copy()
    for name in layer.StackAddressed():
      mine[name] = th[name][s * held:(s + 1) * held]
    out, counts = layer.FPropWithCounts(mine, x)
    assert counts.shape == (held,)
    counted += int(counts.sum())
    total = total + (out - x - shared)
  assert counted == x.shape[0] * tpl.num_experts_per_token
  np.testing.assert_allclose(np.asarray(x + total + shared), np.asarray(want),
                             atol=2e-5)
