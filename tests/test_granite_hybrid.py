"""granite-4.0-h-small's mechanisms at a size the CPU holds, against the plain
reference (benchmarks/references/granite_hybrid.py): a published layer as
two one-branch layers under a residual multiplier, Mamba-2 with ONE group for
all heads (the row pass tiling a group's channels), softmax-weighted gated
experts beside a shared one with a SHARE of them held in a `BlockSequence`,
the embedding's, the scores' and the logits' constants, a tied head, and the
tiny registered sibling served by ServingLoop in chunks and decode steps
through slot state and one layer's pages."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import granite_hybrid as ref
from benchmarks.references import nemotron_h as nemotron_ref
from lingvo_tpu import model_registry
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.core import ssm as ssm_lib
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.models.lm import layers as lm_layers
from lingvo_tpu.models.lm.params import granite_hybrid
from lingvo_tpu.ops import packed_ssd_scan
from lingvo_tpu.serving import engine as engine_lib

import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)

# the served f32 model against the f32 reference: both sum the same products
# in another order (chunked scan, sorted experts, paged attention), which
# reads 1e-6 on logits of about 0.1; the same weights rounded to bf16 read
# 1e-3 and more (test_bf16_weights_fail_the_tolerance)
_LOGIT_TOL = 2e-4
# the share the tests hold: experts 2-5 of the tiny twin's 8 (a run that does
# not start at 0), as one of two chips that share each layer would
_FIRST, _HELD = 2, 4


def _Task(model="lm.granite_hybrid.Granite40HSmallTiny", dtype=None,
          **task_params):
  mp = model_registry.GetParams(model, "Train")
  tp = mp.task
  tp.input = mp.input
  if dtype is not None:
    tp.fprop_dtype = dtype
  for key, value in task_params.items():
    tp.SetPath(key.replace("__", "."), value)
  task = tp.Instantiate()
  task.FinalizePaths()
  return task


_SHARE = {"expert_ffn_tpl__first_expert": _FIRST,
          "expert_ffn_tpl__num_experts_held": _HELD}


def _Seeded(task, first=_FIRST, key=7):
  return ref.SeededWeights(task.InstantiateVariables(jax.random.PRNGKey(key)),
                           first_expert=first)


@pytest.fixture(scope="module")
def tiny():
  """(task, theta): one period of 20 branches with experts 2-5 of 8 held."""
  task = _Task(**_SHARE)
  return task, _Seeded(task)


def _ReferenceLogits(theta, seq, at, width=128, first=_FIRST):
  ref._STATED["first_expert"] = first
  ids = np.zeros((1, width), np.int32)
  ids[0, :len(seq)] = seq
  return np.asarray(jax.jit(lambda th, i, a: ref.LogitsAt(th, i, a, 0.0))(
      theta, jnp.asarray(ids), jnp.asarray([at], jnp.int32)))[0]


def _Forward(task, theta, ids):
  return np.asarray(task.ComputePredictions(theta, NestedMap(
      ids=jnp.asarray(ids), paddings=jnp.zeros(ids.shape))).logits)


# -- the stack as data ---------------------------------------------------------


@pytest.mark.parametrize("branches", [20, 40, 80])
def test_a_two_branch_layer_is_written_as_its_two_letters(branches):
  pattern = granite_hybrid.Granite40HSmall.PATTERN
  assert len(pattern) == 80 and pattern.count("*") == 4
  # layer_types: attention at published layers 5, 15, 25, 35
  assert [i // 2 for i, c in enumerate(pattern) if c == "*"] == [5, 15, 25, 35]
  assert pattern[1::2] == "E" * 40
  kinds = [lm_layers.PATTERN_KINDS[c] for c in pattern[:branches]]
  got = lm_layers.KindBlocks(kinds)
  assert [k for ks, r in got for k in ks * r] == kinds
  if branches == 20:
    # one period: what the cell runs
    assert got == [(["mamba2", "experts"], 5), (["gqa"], 1),
                   (["experts", "mamba2"], 4), (["experts"], 1)]
  else:
    # the whole period repeats: one scanned block of twenty branches
    assert got == [(kinds[:20], branches // 20)]


def test_the_published_model_counts_its_parameters_from_shapes():
  mp = model_registry.GetParams("lm.granite_hybrid.Granite40HSmall", "Train")
  tp = mp.task
  tp.input = mp.input
  task = tp.Instantiate()
  specs = jax.tree_util.tree_leaves(task.VariableSpecs())
  total = sum(int(np.prod(s.shape)) for s in specs)
  mamba = 4096 * 16768 + 8192 * 4096 + 5 * 8448 + 3 * 128 + 8192 + 4096
  atten = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 4096
  experts = 4096 * 72 + 72 * 3 * 4096 * 768 + 3 * 4096 * 1536 + 4096
  want = 36 * mamba + 4 * atten + 40 * experts + 100352 * 4096 + 4096
  assert total == want
  assert 32.0e9 < total < 32.5e9          # "32B-A9B"


# -- (a) the whole model -------------------------------------------------------


@pytest.mark.parametrize("row,at", [(0, 63), (1, 30), (1, 2)])
def test_whole_model_forward_is_the_references(tiny, row, at):
  task, theta = tiny
  ids = np.random.RandomState(4).randint(1, 128, (2, 64)).astype(np.int32)
  logits = _Forward(task, theta, ids)
  np.testing.assert_allclose(logits[row, at],
                             _ReferenceLogits(theta, ids[row], at),
                             atol=_LOGIT_TOL)


# -- (d) each of the four constants is seen ------------------------------------


@pytest.mark.parametrize("key,neutral", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0), ("atten_tpl__score_scale", None)])
def test_a_multiplier_left_out_fails_the_tolerance(tiny, key, neutral):
  """The program with one constant at its neutral value against the
  reference that has it. The attention layer's output is scaled by 64 (as
  the cell's file scales it by 16): one attention layer of ten over
  near-uniform keys, times 0.22, else moves the logits by 3e-5."""
  task, _ = tiny
  theta = ref.SeededWeights(
      task.InstantiateVariables(jax.random.PRNGKey(7)),
      attention_out_scale=64.0, first_expert=_FIRST)
  broken = _Task(**_SHARE, **{key: neutral})
  ids = np.random.RandomState(4).randint(1, 128, (1, 64)).astype(np.int32)
  want = _ReferenceLogits(theta, ids[0], 63)
  assert np.abs(_Forward(task, theta, ids)[0, 63] - want).max() < _LOGIT_TOL
  assert np.abs(_Forward(broken, theta, ids)[0, 63] - want).max() > (
      2 * _LOGIT_TOL), key


def test_defaults_of_the_new_params_are_neutral():
  """A stack that states none of them runs no op of theirs: the other
  configurations' programs are what they were."""
  task = _Task("lm.nemotron_h.Nemotron3NanoTiny")
  assert task.p.embedding_multiplier == task.p.logits_scaling == 1.0
  assert task.p.residual_multiplier == 1.0
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))
  ids = jnp.asarray(np.random.RandomState(1).randint(1, 128, (1, 16)))
  text = jax.jit(lambda th: task.ComputePredictions(th, NestedMap(
      ids=ids, paddings=jnp.zeros(ids.shape))).logits).lower(theta).as_text()
  # 0.22, 12 and 1/16 appear nowhere in the lowered program
  for constant in ("2.200000e-01", "1.200000e+01", "6.250000e-02"):
    assert constant not in text


# -- (b) the tiny sibling through ServingLoop ----------------------------------


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
    logits = np.asarray(logits[0].astype(jnp.float32))
    for col in np.flatnonzero(np.asarray(rows.valid)):
      key = int(np.asarray(rows.row_of)[col]), int(np.asarray(rows.pos)[col])
      self.seen[key] = logits[col]
    counts = jnp.concatenate(engine_lib._MoeCountLeaves(new_states), axis=0)
    elsewhere = engine_lib._MoeCountLeaves(new_states, "elsewhere", 1)
    if elsewhere is not None:
      counts = jnp.concatenate(
          [counts, jnp.concatenate(elsewhere, axis=0)], axis=1)
    return jnp.asarray(logits.argmax(-1), jnp.int32), counts, new_states


def _Serve(task, theta, prompts, new_tokens):
  eng = engine_lib.ServingLoop(task, theta, page_size=8, num_pages=48,
                               max_batch=len(prompts), max_seq_len=128,
                               prefill_token_budget=16)
  probe = _Probe(eng, task)
  handles = [eng.Submit(p, new_tokens) for p in prompts]
  for _ in range(600):
    if all(h.done for h in handles):
      break
    eng.StepOnce()
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


@pytest.fixture(scope="module")
def served(tiny):
  cache = {}

  def _Get(case):
    if case not in cache:
      cache[case] = _Serve(*tiny, _Prompts(case), 8)
    return cache[case]

  return _Get


@pytest.mark.parametrize("case", sorted(_PROMPTS))
def test_chunked_prefill_and_decode_match_the_reference(tiny, served, case):
  """Prefill in chunks (a budget of 16 shared by the rows of a step) and 8
  decode steps through slot state and one layer's pages: the step's logits
  at the end of the prompt and at the last token fed back equal the
  reference's full forward there (logits, not sampled tokens)."""
  _, theta = tiny
  eng, probe, outs = served(case)
  for slot, (prompt, out) in enumerate(zip(_Prompts(case), outs)):
    seq = np.concatenate([prompt, np.asarray(out, np.int32)])
    for at in (len(prompt) - 1, len(seq) - 2):
      np.testing.assert_allclose(
          probe.seen[slot, at], _ReferenceLogits(theta, seq, at),
          atol=_LOGIT_TOL, err_msg=f"row {slot} position {at}")
  stats = eng.Stats()
  assert stats["kv_pages"]["in_use"] == 0
  assert stats["state_slots"]["in_use"] == 0


def test_bf16_weights_fail_the_tolerance(tiny):
  """The tolerance sees the nearest precision below the one the test
  states: the same model with its weights rounded to bf16."""
  task, theta = tiny
  rounded = jax.tree_util.tree_map(
      lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
      if jnp.issubdtype(x.dtype, jnp.floating) else x, theta)
  ids = np.random.RandomState(4).randint(1, 128, (1, 64)).astype(np.int32)
  got = _Forward(task, rounded, ids)[0, 63]
  assert np.abs(got - _ReferenceLogits(theta, ids[0], 63)).max() > 2 * _LOGIT_TOL


# -- (c) the share, tied to the model ------------------------------------------


def test_a_block_sequence_counts_the_pairs_of_its_share(tiny, served):
  """Ten expert layers that each hold 4 of 8 experts, top-3: every valid
  token's three pairs are counted once a layer, here or elsewhere, and the
  step records carry both beside the Mamba-2 layers' state rows."""
  task, _ = tiny
  eng, _, outs = served("uneven_chunks_in_one_step")
  stats = eng.Stats()
  tokens = sum(_PROMPTS["uneven_chunks_in_one_step"]) + sum(
      len(o) - 1 for o in outs)
  assert stats["moe_pairs_elsewhere"] > 0 and stats["moe_tokens_routed"] > 0
  assert (stats["moe_tokens_routed"] + stats["moe_pairs_elsewhere"]
          == 10 * 3 * tokens)
  kinds = task.stack.LayerKinds()
  assert kinds == {"Mamba2Layer": 9, "DroplessMoELayer": 10,
                   "PooledAttention": 1}
  # a live row reads and writes the state of each of the nine Mamba-2 layers
  assert stats["ssd_state_rows"] % 9 == 0 and stats["ssd_state_rows"] > 0
  # ... and those of one token are the row pass's narrow body's: the decode
  # rows, and never the chunks of the prompts
  assert stats["ssd_narrow_rows"] % 9 == 0
  assert 0 < stats["ssd_narrow_rows"] < stats["ssd_state_rows"]
  # a live row's first K - 1 = 3 tokens of a step read its convolution tail:
  # one in a decode row, up to three in a chunk of a prompt
  assert stats["conv_tail_tokens"] % 9 == 0
  assert (stats["ssd_state_rows"] < stats["conv_tail_tokens"]
          < 3 * stats["ssd_state_rows"])
  records = eng._recorder.Steps() if getattr(eng, "_recorder", None) else []
  for rec in records[-1:]:
    assert "ssd_state_rows" in rec.counters
    assert "ssd_narrow_rows" in rec.counters
    assert "conv_tail_tokens" in rec.counters
    assert "moe_pairs_elsewhere" in rec.counters


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_routed_parts_add_up_to_the_uncut_layer(shares,
                                                            monkeypatch):
  """The tiny twin's expert layer (8 gated experts top-3 weighed by the
  softmax over the three chosen logits, a shared expert, the router on the
  layer's own normed input, the residual multiplier) cut into contiguous
  runs: the routed parts of all the shares plus the shared expert ONCE are
  the uncut layer's output as the reference computes it."""
  task = _Task()
  theta = _Seeded(task, first=0)
  ff = task.stack.block_3.x_layers[0].fflayer
  tpl = ff.p.Copy()
  th = jax.tree_util.tree_map(
      lambda a: a[0], theta.stack["block_3"].x_layers[0].fflayer)
  e, d, f = tpl.num_experts, tpl.input_dim, tpl.residual_scale
  assert f == 0.22
  monkeypatch.setattr(ref, "_PIECE", 4)
  ref._ARCH.clear()
  ref._ARCH.update(ref._Arch(d))
  x = jnp.asarray(np.random.RandomState(shares).randn(19, d), jnp.float32)
  layer_ff = {"fflayer": jax.tree_util.tree_map(lambda a: a[None], dict(th))}
  want = ref._Experts(layer_ff, 0, x, 1)
  u = ref._RmsNorm(x, th.ln.scale)
  shared = f * ref._Gated(u, th.w_shared_gate, th.w_shared_up,
                          th.w_shared_down)
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
    counted += int(counts.sum())
    total = total + (out - x - shared)
  assert counted == x.shape[0] * tpl.num_experts_per_token
  np.testing.assert_allclose(np.asarray(x + total + shared), np.asarray(want),
                             atol=2e-5)


# -- (f) a configuration that existed before the refactor ----------------------


@pytest.mark.parametrize("first", [0, 4])
def test_nemotrons_tiny_twin_serves_a_share_of_its_experts(first):
  """Nemotron3NanoTiny (PR 45) with experts [first, first + 4) of 8 held in
  its `E` layers: served through ServingLoop its logits are the whole-sequence
  forward's of the same share, and the pairs elsewhere are counted."""
  task = _Task("lm.nemotron_h.Nemotron3NanoTiny",
               expert_ffn_tpl__first_expert=first,
               expert_ffn_tpl__num_experts_held=4)
  theta = nemotron_ref.SeededWeights(
      task.InstantiateVariables(jax.random.PRNGKey(3)), router_bias_spread=0.5)
  prompts = [np.random.RandomState(first + n).randint(1, 128, n).astype(
      np.int32) for n in (21, 50)]
  eng, probe, outs = _Serve(task, theta, prompts, 6)
  for slot, (prompt, out) in enumerate(zip(prompts, outs)):
    seq = np.concatenate([prompt, np.asarray(out, np.int32)])[None]
    want = _Forward(task, theta, seq)[0]
    for at in (len(prompt) - 1, seq.shape[1] - 2):
      np.testing.assert_allclose(probe.seen[slot, at], want[at],
                                 atol=_LOGIT_TOL)
  stats = eng.Stats()
  tokens = sum(len(p) for p in prompts) + sum(len(o) - 1 for o in outs)
  assert stats["moe_pairs_elsewhere"] > 0
  assert (stats["moe_tokens_routed"] + stats["moe_pairs_elsewhere"]
          == 4 * 3 * tokens)


# -- (e) the row pass tiles a group's channels ---------------------------------


def _ScanInputs(hm, p, g, n, slots=5, t=40, seed=0):
  rng = np.random.RandomState(seed)
  f32 = lambda v: jnp.asarray(v, jnp.float32)
  dt = f32(rng.uniform(0.001, 0.5, (t, hm)))
  x, b, c = (f32(rng.randn(t, *k)) for k in ((hm, p), (g, n), (g, n)))
  a = -f32(rng.uniform(1, 16, hm))
  d, state = f32(rng.randn(hm)), f32(rng.randn(slots, hm, p, n))
  return x, dt, a, b, c, d, state


_ROWS = {
    "mixed": ((1, 0, 16, 1, 7), (5, 9, 0, 0, 3)),
    "decode_only": ((1, 1, 1, 1, 1), (4, 0, 9, 2, 7)),
    "across_chunks": ((3, 21, 0, 9, 5), (0, 7, 1, 0, 2)),
    "long_row": ((0, 38, 0, 0, 0), (1, 0, 1, 1, 1)),
    # one-token rows at a scan chunk's two edges: slot 1's token is the LAST
    # of chunk 0 (index 7 of 8), slot 2's the FIRST of chunk 1
    "decode_at_chunk_edges": ((7, 1, 1, 1, 1), (0, 3, 5, 0, 9)),
    # the step's only chunk row (tokens 2..21) came into its last chunk from
    # an earlier one; every other slot's hand-over index stands still
    "one_row_came_in": ((1, 1, 20, 1, 1), (4, 2, 6, 0, 3)),
    # slots without a token beside one-token rows (one a request's first)
    "idle_beside_decode": ((0, 1, 0, 1, 0), (2, 5, 1, 0, 7)),
}


def _Rows(case):
  row_len, q_pos = _ROWS[case]
  rows = ragged_lib.BuildRaggedRows(np.array(row_len), np.array(q_pos), 40, 38)
  return ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows))


@pytest.mark.parametrize("twin", ["sequential", "xla"])
@pytest.mark.parametrize("case", sorted(_ROWS))
def test_one_group_wider_than_a_channel_tile(case, twin):
  """G = 1, 16 heads of 64: a group of 1,024 channels is two tiles of 512,
  both reading the group's one B and C (interpret mode); rows that start,
  continue and end inside a scan chunk of 8."""
  assert packed_ssd_scan.ChannelTile(1024) == 512
  args, rows = _ScanInputs(16, 64, 1, 128), _Rows(case)
  y, s = packed_ssd_scan.PackedSsdScan(*args, rows, chunk_size=8,
                                       lowering="pallas")
  want_y, want_s = packed_ssd_scan.PackedSsdScan(*args, rows, chunk_size=8,
                                                 lowering=twin)
  np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=3e-5)
  np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=3e-5)


@pytest.mark.parametrize("channels,tile", [
    (512, 512), (8192, 512), (1024, 512), (384, 384), (640, 128), (128, 128)])
def test_channel_tiles_divide_a_group(channels, tile):
  assert packed_ssd_scan.ChannelTile(channels) == tile
  assert packed_ssd_scan.SupportedOnTpu(64, channels, 128)


def _RowPassGrids(hm, p, g, n):
  """The grid and the state's block of every pallas_call the scan traces."""
  args, rows = _ScanInputs(hm, p, g, n), _Rows("mixed")
  jaxpr = jax.make_jaxpr(lambda *a: packed_ssd_scan.PackedSsdScan(
      *a, rows, chunk_size=8, lowering="pallas", interpret=True))(*args)
  found = []

  def _Walk(j):
    for eqn in j.eqns:
      if eqn.primitive.name == "pallas_call":
        gm = eqn.params["grid_mapping"]
        found.append((tuple(gm.grid), [
            [int(getattr(b, "block_size", b)) for b in bm.block_shape]
            for bm in gm.block_mappings]))
      for sub in jax.core.jaxprs_in_params(eqn.params):
        _Walk(sub)

  _Walk(jaxpr.jaxpr)
  return found


@pytest.mark.parametrize("hm,g,tiles", [(64, 8, 8), (128, 1, 16)])
def test_nemotrons_groups_keep_their_grid_and_blocks(hm, g, tiles):
  """At `nemotron3nano`'s G = 8, W = 512 a group is ONE tile: the grid is
  (slots, groups) and the state's block [1, 512, N], as before the pass
  tiled; at Granite's G = 1 the same block walks one group's 16 tiles.
  Since PR 58 ONE call with two bodies: beside the chunk body's [q, .]
  blocks the one-token body's [8, .] blocks (the token's operands in, its
  read-out out)."""
  (grid, blocks), = _RowPassGrids(hm, 64, g, 128)
  assert grid == (5, tiles)
  state, tokens, groups, heads = [1, 512, 128], [1, 8, 512], [1, 8, 128], hm
  assert blocks == [
      state, state,                                # the slot's, the hand-over
      [1, 8, 128], [1, 8, heads], tokens, [1, 8, heads], [1, 8, 128],
      [1, 8, heads], [heads, 512],                 # the chunk body's (q = 8)
      tokens, groups,                              # the one-token body's
      tokens, state, tokens]                       # y_rows, the state, y_tok


@pytest.mark.parametrize("twin", ["xla", "sequential"])
@pytest.mark.parametrize("case", sorted(_ROWS))
def test_nemotrons_groups_read_what_the_xla_twin_reads(case, twin):
  args, rows = _ScanInputs(16, 64, 2, 128), _Rows(case)   # W = 512, G = 2
  y, s = packed_ssd_scan.PackedSsdScan(*args, rows, chunk_size=8,
                                       lowering="pallas")
  want_y, want_s = packed_ssd_scan.PackedSsdScan(*args, rows, chunk_size=8,
                                                 lowering=twin)
  np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=3e-5)
  np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=3e-5)


@pytest.mark.parametrize("g", [1, 2])
@pytest.mark.parametrize("case", ["long_row", "idle_beside_decode"])
def test_a_row_with_no_token_keeps_its_state_to_the_bit(case, g):
  """A slot without a token in the step (not a request's first) is read and
  written back unchanged, beside a chunk row's body and, in a step whose
  chunk body's operands all stand still, beside one-token rows."""
  row_len, rows = _ROWS[case][0], _Rows(case)
  args = _ScanInputs(16, 64, g, 128)
  _, s = packed_ssd_scan.PackedSsdScan(*args, rows, chunk_size=8,
                                       lowering="pallas")
  idle = [i for i, n in enumerate(row_len) if n == 0]
  np.testing.assert_array_equal(np.asarray(s)[idle],
                                np.asarray(args[-1])[idle])
  busy = [i for i, n in enumerate(row_len) if n > 0]
  assert busy and not np.array_equal(np.asarray(s)[busy],
                                     np.asarray(args[-1])[busy])


@pytest.mark.parametrize("case", ["decode_only", "one_row_came_in"])
def test_each_cut_alone_is_the_kernel_with_none(case):
  """`CUTS`, what `tools/kernel_probe.py` switches one at a time: the pass
  with any one of them reads what the pass with none reads."""
  args, rows = _ScanInputs(16, 64, 1, 128), _Rows(case)
  *ops, state = args

  def _Scan(cuts):
    row_pass = functools.partial(packed_ssd_scan._PallasRowPass, g=1,
                                 interpret=True, cuts=cuts)
    return packed_ssd_scan._ChunkedPackedScan(*ops, state[None], rows, 8,
                                              row_pass, 0)

  want_y, want_s = _Scan(())
  for cut in packed_ssd_scan.CUTS:
    y, s = _Scan((cut,))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=3e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=3e-5)


def test_the_kernel_probe_builds_the_row_pass_and_runs_it(capsys):
  """tools/kernel_probe.py --case row_pass at the CPU's rehearsal sizes:
  its module's chunked form builds the kernel's operands, the kernel runs
  alone (interpret mode) and every variant is held to the first."""
  import importlib.util
  import json
  import os
  spec = importlib.util.spec_from_file_location("kernel_probe", os.path.join(
      os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools",
      "kernel_probe.py"))
  kernel_probe = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(kernel_probe)
  assert kernel_probe.main(["--case", "row_pass", "--tiny", "--calls", "1",
                            "--shapes", "granite", "--steps", "chunk",
                            "--variants", "none,all"]) == 0
  lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
           if l.startswith("{")]
  assert [l["variant"] for l in lines] == ["none", "all"]
  assert lines[1]["within_3e-5"] and lines[0]["heads"] == 16
  assert all(l["tiny"] and l["device"]["platform"] == "cpu" for l in lines)


@pytest.mark.parametrize("lowering", ["pallas", "xla", "sequential"])
@pytest.mark.parametrize("case", ["mixed", "across_chunks"])
def test_a_layers_states_are_read_and_written_where_they_lie(case, lowering):
  """`layer`: the state is a scanned block's stack [L, B, Hm, P, N]; the
  call reads and writes layer 1's, and the other layers' come back to the
  bit."""
  args, rows = _ScanInputs(16, 64, 1, 128), _Rows(case)
  *ops, state = args
  stack = jnp.stack([state + 1.0, state, state - 1.0])
  want_y, want_s = packed_ssd_scan.PackedSsdScan(
      *ops, state, rows, chunk_size=8, lowering=lowering)
  y, new = packed_ssd_scan.PackedSsdScan(
      *ops, stack, rows, chunk_size=8, lowering=lowering,
      layer=jnp.asarray(1, jnp.int32))
  np.testing.assert_array_equal(np.asarray(y), np.asarray(want_y))
  np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(want_s))
  np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(stack[0]))
  np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(stack[2]))


def test_the_tiny_twins_scan_states_ride_their_blocks_stacks(tiny):
  task, _ = tiny
  layers = [l for body in task.stack._bodies for l in body]
  assert [l.StackStates() for l in layers if l.mixer is not None
          and hasattr(l.mixer, "stack_states")] == [("scan",)] * 2
  # every Mamba-2 layer's: the configuration that had the scan before this
  # one rides the same path
  nemotron = _Task("lm.nemotron_h.Nemotron3NanoTiny")
  assert {l.StackStates() for body in nemotron.stack._bodies for l in body
          if isinstance(l.mixer, ssm_lib.Mamba2Layer)} == {("scan",)}
