"""Phi-4-mini-flash-reasoning's mechanisms at a size the CPU holds, against the
plain reference (benchmarks/references/phi4flash.py) and against numpy: the
selective scan on the packed axis, differential attention over pages, the
page writes, every kind of layer, the whole model, and the tiny registered
sibling served by ServingLoop in chunks and decode steps through pages that
nine layers own and seven more read, and through slot state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import phi4flash as ref
from lingvo_tpu import model_registry
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.core.nested_map import NestedMap
from lingvo_tpu.models.lm import layers as lm_layers
from lingvo_tpu.models.lm.params import phi4flash
from lingvo_tpu.ops import diff_attend
from lingvo_tpu.ops import selective_scan
from lingvo_tpu.serving import engine as engine_lib
from lingvo_tpu.serving import kv_cache
from lingvo_tpu.serving import spec_decode

import lingvo_tpu.models.all_params  # noqa: F401  (fills the registry)

_WINDOW = 24                 # Phi4MiniFlashTiny's: 3 pages of 8
# the served f32 model against the f32 reference; the same weights rounded
# to bf16 read 1e-2 and more (test_bf16_weights_fail_the_tolerance)
_LOGIT_TOL = 2e-4
# bf16 activations and weights against the f32 reference of the same
# (rounded) weights: what is left is the activations' rounding, 3 digits on
# logits of about 3 through 8 to 12 layers
_BF16_TOL = 0.12


def _Task(depth, dtype=None):
  mp = model_registry.GetParams("lm.phi4flash.Phi4MiniFlashTiny", "Train")
  tp = mp.task
  tp.input = mp.input
  tp.num_layers = depth
  tp.layer_kinds = phi4flash.LayerKinds(depth)
  if dtype is not None:
    tp.fprop_dtype = dtype
  task = tp.Instantiate()
  task.FinalizePaths()
  return task


@pytest.fixture(scope="module")
def tiny():
  """{depth: (task, theta)}: 8 layers hold every kind once; 12 scan the
  cross-decoder's block twice and the window block three times."""
  out = {}
  for depth in (8, 12):
    task = _Task(depth)
    theta = task.InstantiateVariables(jax.random.PRNGKey(7))
    out[depth] = task, ref.SeededWeights(theta, attention_out_scale=4.0,
                                         window=_WINDOW)
  return out


# -- the stack as data ---------------------------------------------------------


@pytest.mark.parametrize("depth,blocks", [
    (8, [(["mamba", "window"], 2), (["mamba_export"], 1), (["full"], 1),
         (["gmu"], 1), (["cross"], 1)]),
    (12, [(["mamba", "window"], 3), (["mamba_export"], 1), (["full"], 1),
          (["gmu", "cross"], 2)]),
    (32, [(["mamba", "window"], 8), (["mamba_export"], 1), (["full"], 1),
          (["gmu", "cross"], 7)]),
])
def test_layer_kinds_by_depth_and_their_blocks(depth, blocks):
  kinds = phi4flash.LayerKinds(depth)
  assert len(kinds) == depth and kinds[depth // 2] == "mamba_export"
  assert kinds[depth // 2 + 1] == "full" and kinds.count("full") == 1
  assert lm_layers.KindBlocks(kinds) == blocks


def test_published_model_counts_its_layers_and_parameters():
  mp = model_registry.GetParams("lm.phi4flash.Phi4MiniFlash", "Train")
  tp = mp.task
  tp.input = mp.input
  task = tp.Instantiate()
  task.FinalizePaths()
  shapes = jax.eval_shape(task.InstantiateVariables, jax.random.PRNGKey(0))
  total = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
  assert total == 3_852_457_984                 # the published 3.8B
  stack = task.stack
  # nine layers own pages (eight of the window, one full), seven read
  assert stack.PageWindows() == [512] * 8 + [0]
  assert stack.SharedKvReadLayers() == 7
  census = kv_cache.StackCensus(task)
  assert census["num_ssm"] == 9
  assert census["decode_state_bytes_per_slot"] == 9 * 4 * 5120 * (16 + 3)


# -- the selective scan on the packed axis ------------------------------------


def _NaiveScan(delta, x, b, c, a, d, state, row_len, q_pos):
  t, e = delta.shape
  y, s_out, cur = np.zeros((t, e)), np.array(state), 0
  for i, n in enumerate(row_len):
    if n == 0:
      continue
    s = np.zeros_like(s_out[i]) if q_pos[i] == 0 else np.array(state[i])
    for j in range(n):
      k = cur + j
      s = np.exp(delta[k][None] * a) * s + (delta[k] * x[k])[None] * b[k][:, None]
      y[k] = (s * c[k][:, None]).sum(0) + d * x[k]
    s_out[i], cur = s, cur + n
  return y, s_out


@pytest.mark.parametrize("lowering", ["xla", "pallas"])
@pytest.mark.parametrize("row_len,q_pos", [
    ((1, 0, 16, 1, 7), (5, 9, 0, 0, 3)),          # decode rows beside chunks
    ((1, 1, 1, 1, 1), (4, 0, 9, 2, 7)),           # a decode-only step
    ((0, 0, 13, 0, 0), (3, 3, 40, 3, 3)),         # one chunk, not from zero
], ids=["mixed", "decode_only", "one_chunk"])
def test_selective_scan_on_the_packed_axis(lowering, row_len, q_pos):
  rng = np.random.RandomState(0)
  b_, n_, e_, t_, w_ = 5, 8, 128, 40, 16
  rows = ragged_lib.BuildRaggedRows(np.array(row_len), np.array(q_pos), t_, w_)
  rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows))
  f32 = lambda v: np.asarray(v, np.float32)
  delta = f32(rng.uniform(0.001, 0.1, (t_, e_)))
  x, b, c = (f32(rng.randn(t_, k)) for k in (e_, n_, n_))
  a = -f32(np.tile(np.arange(1, n_ + 1)[:, None], (1, e_)))
  d, state = f32(rng.randn(e_)), f32(rng.randn(b_, n_, e_))
  y, s = selective_scan.SelectiveScan(
      *(jnp.asarray(v) for v in (delta, x, b, c, a, d, state)), rows,
      lowering=lowering)
  want_y, want_s = _NaiveScan(delta, x, b, c, a, d, state, row_len, q_pos)
  np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5)
  np.testing.assert_allclose(np.asarray(s), want_s, atol=2e-5)


# -- differential attention over pages, and the page writes -------------------


def _NumpyDiffAttend(q, kp, vp, tables, row_of, q_end, lam, page, window):
  t, n, h = q.shape
  nk = kp.shape[2]
  group = (n // 2) // (nk // 2)
  out = np.zeros((t, n // 2, 2 * h), np.float32)
  for i in range(t):
    e = int(q_end[i])
    if e == 0:
      continue
    slots = range(max(0, e - window) if window else 0, e)
    ks = np.stack([kp[tables[row_of[i], s // page], s % page] for s in slots])
    vs = np.stack([vp[tables[row_of[i], s // page], s % page] for s in slots])
    for j in range(n // 2):
      pair = j // group
      wide = vs[:, 2 * pair:2 * pair + 2].reshape(len(ks), 2 * h)
      both = []
      for r in range(2):
        sc = ks[:, 2 * pair + r] @ q[i, 2 * j + r]
        p = np.exp(sc - sc.max())
        both.append((p / p.sum()) @ wide)
      out[i, j] = both[0] - lam * both[1]
  return out


@pytest.mark.parametrize("lowering", ["xla", "pallas"])
@pytest.mark.parametrize("window", [0, 20])
def test_diff_attend_is_two_softmaxes_a_pair(lowering, window):
  rng = np.random.RandomState(1)
  page, num_pages, nq, nk, h = 16, 40, 8, 4, 8
  row_of, q_end = [], []
  for i, (n, e) in enumerate([(1, 51), (20, 31), (5, 1)]):
    row_of += [i] * n
    q_end += list(range(e, e + n))
  row_of, q_end = np.array(row_of + [0] * 3), np.array(q_end + [0] * 3)
  f32 = lambda *s: np.asarray(rng.randn(*s), np.float32)
  q = f32(len(row_of), nq, h)
  kp, vp = f32(num_pages, page, nk, h), f32(num_pages, page, nk, h)
  tables = rng.permutation(num_pages - 1)[:24].reshape(3, 8).astype(np.int32)
  got = diff_attend.DiffAttend(
      *(jnp.asarray(v) for v in (q, kp, vp, tables, row_of, q_end)), 0.3,
      page_size=page, window=window, lowering=lowering)
  want = _NumpyDiffAttend(q, kp, vp, tables, row_of, q_end, 0.3, page, window)
  np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_page_writes_land_where_the_scatter_puts_them(dtype):
  """The chip's page writes (a program a (row, page) pair the step writes,
  whole pages rewritten; a 16-bit pool's lanes moved as 32-bit words)
  against the scatter: a decode row, a row that crosses two page boundaries,
  a row that starts a page, a row with nothing."""
  rng = np.random.RandomState(2)
  page, num_pages, nk, h, t_ = 16, 60, 4, 8, 48
  rows = ragged_lib.BuildRaggedRows(np.array([1, 0, 30, 5]),
                                    np.array([37, 9, 10, 0]), t_, 32)
  rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows))
  rand = lambda *s: jnp.asarray(rng.randn(*s), dtype)
  kp, vp = rand(num_pages, page, nk, h), rand(num_pages, page, nk, h)
  kn, vn = rand(t_, nk, h), rand(t_, nk, h)
  tables = jnp.asarray(rng.permutation(num_pages - 1)[:32].reshape(4, 8),
                       jnp.int32)
  want = diff_attend.WritePages(kp, vp, kn, vn, tables, rows, lowering="xla")
  got = diff_attend.WritePages(kp, vp, kn, vn, tables, rows,
                               lowering="pallas")
  for a, b, old in zip(got, want, (kp, vp)):
    # all but the trash page, which only the scatter's padding writes
    a, b, old = (np.asarray(x.astype(jnp.float32)) for x in (a, b, old))
    np.testing.assert_array_equal(a[:-1], b[:-1])
    assert int((a[:-1] != old[:-1]).any(axis=(2, 3)).sum()) == 36  # the
    #                                              valid tokens' slots
    np.testing.assert_array_equal(a[-1], old[-1])  # nothing writes the trash
  assert diff_attend.PageWrites(64, 576, 128) == 132


# -- every kind of layer, and the whole model, against the reference ----------


def _RefArch():
  ref._ARCH.clear()
  ref._ARCH.update(window=_WINDOW, eps=1e-5)


@pytest.mark.parametrize("kind", ["mamba", "window", "mamba_export", "full",
                                  "gmu", "cross"])
def test_a_layer_of_each_kind_is_the_references(tiny, kind):
  """One layer's mixer block, h + Mixer(LN(h)), in the program and in the
  reference, from the same weights, on the same stream."""
  task, theta = tiny[8]
  kinds = phi4flash.LayerKinds(8)
  depth = kinds.index(kind)
  blocks, at = lm_layers.KindBlocks(kinds), 0
  for b, (names, reps) in enumerate(blocks):
    if at <= depth < at + len(names) * reps:
      rep, j = divmod(depth - at, len(names))
      break
    at += len(names) * reps
  layer = getattr(task.stack, f"block_{b}").x_layers[j]
  weights = theta.stack[f"block_{b}"].x_layers[j]
  mine = jax.tree_util.tree_map(lambda x: x[rep], weights)
  rng = np.random.RandomState(3)
  s_len, d = 40, 48
  x = jnp.asarray(rng.randn(s_len, d), jnp.float32)
  memory = jnp.asarray(rng.randn(s_len, 96), jnp.float32)
  key, value = (jnp.asarray(rng.randn(s_len, 4, 8), jnp.float32)
                for _ in range(2))
  shared = NestedMap(memory=memory[None], key=key[None], value=value[None])
  normed = layer.ln.FProp(mine.ln, x[None])
  out, shared = layer.atten.FProp(mine.atten, normed, shared, depth=depth)
  got = np.asarray(x + out[0])
  _RefArch()
  dep = jnp.asarray(depth)
  if kind.startswith("mamba"):
    want, y = ref._Mamba(weights, rep, x, 1, s_len)
    if kind == "mamba_export":
      np.testing.assert_allclose(np.asarray(shared.memory[0]), np.asarray(y),
                                 atol=2e-5)
  elif kind == "gmu":
    want = ref._MemoryUnit(weights, rep, x, memory, 1, s_len)
  elif kind == "cross":
    want = ref._Attention(weights, rep, dep, x, key, value, 1, s_len, False)
  else:
    kv = ref._KeysValues(weights, rep, x, 1, s_len)
    want = ref._Attention(weights, rep, dep, x, *kv, 1, s_len,
                          kind == "window")
    if kind == "full":
      np.testing.assert_allclose(np.asarray(shared.key[0]), np.asarray(kv[0]),
                                 atol=2e-5)
  np.testing.assert_allclose(got, np.asarray(want), atol=5e-5)


def _ReferenceLogits(theta, seq, at, width=128):
  ids = np.zeros((1, width), np.int32)
  ids[0, :len(seq)] = seq
  return np.asarray(jax.jit(lambda th, i, a: ref.LogitsAt(th, i, a, 0.0))(
      theta, jnp.asarray(ids), jnp.asarray([at], jnp.int32)))[0]


@pytest.mark.parametrize("depth", [8, 12])
def test_whole_model_forward_is_the_references(tiny, depth):
  task, theta = tiny[depth]
  ids = np.random.RandomState(4).randint(1, 128, (2, 64)).astype(np.int32)
  logits = task.ComputePredictions(theta, NestedMap(
      ids=jnp.asarray(ids), paddings=jnp.zeros((2, 64)))).logits
  for row, at in ((0, 63), (1, 30), (1, 2)):
    want = _ReferenceLogits(theta, ids[row], at)
    np.testing.assert_allclose(np.asarray(logits[row, at]), want,
                               atol=_LOGIT_TOL)


# -- the tiny sibling through ServingLoop -------------------------------------


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
    return jnp.asarray(logits.argmax(-1), jnp.int32), new_states


def _PoisonDeadPages(eng):
  """Into the pool, what no query may read: NaN in every page no row holds
  (never handed out, let go of by a window for good, or, for the layers that
  own none, anything outside the full layer's own), a huge number in every
  page a row holds with nothing live in it yet."""
  kp, page = eng._kind_pages, eng.page_size
  held, live = set(), set()
  for seq in eng.sched.slots:
    if seq is not None:
      for layer in range(len(kp.windows)):
        first, pages = kp.Held(seq.id, layer)
        held.update(pages)
        if seq.pos > 0:
          live.update(pages[:(seq.pos - 1) // page - first + 1])
  free = jnp.asarray([p for p in range(kp.alloc.num_pages) if p not in held],
                     jnp.int32)
  stale = jnp.asarray(sorted(held - live), jnp.int32)
  pool = eng._states.kv_pool
  for name in ("key", "value"):
    pool[name] = pool[name].at[free].set(jnp.nan).at[stale].set(3e4)


def _Engine(task, theta, slots, **kw):
  return engine_lib.ServingLoop(task, theta, page_size=8, num_pages=48,
                                max_batch=slots, max_seq_len=128,
                                prefill_token_budget=16, **kw)


def _Serve(task, theta, prompts, new_tokens, poison=False, between=None,
           slots=None):
  eng = _Engine(task, theta, slots or len(prompts))
  probe = _Probe(eng, task)
  handles = [eng.Submit(p, new_tokens) for p in prompts]
  for step in range(600):
    if all(h.done for h in handles):
      break
    eng.StepOnce()
    if poison:
      _PoisonDeadPages(eng)
    if between is not None:
      between(eng, step)
  assert all(h.done for h in handles)
  return eng, probe.seen, [h.Result() for h in handles]


_PROMPTS = {"longer_than_three_windows": [90], "shorter_than_one": [10],
            "uneven_chunks_in_one_step": [90, 10, 50]}


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
  """{(depth, case): (engine, logits seen, streamed tokens)}, served once."""
  cache = {}

  def _Get(depth, case):
    if (depth, case) not in cache:
      task, theta = tiny[depth]
      cache[depth, case] = _Serve(task, theta, _Prompts(case), 8)
    return cache[depth, case]

  return _Get


@pytest.mark.parametrize("depth", [8, 12])
@pytest.mark.parametrize("case", list(_PROMPTS))
def test_chunked_prefill_and_decode_match_the_reference(tiny, served, depth,
                                                        case):
  """Prefill in chunks (a budget of 16 shared by the rows of a step, so
  uneven ones) and 8 decode steps through pages and slot state: the step's
  logits at the end of the prompt and at the last token fed back equal the
  reference's full forward there."""
  _, theta = tiny[depth]
  eng, seen, outs = served(depth, case)
  _HoldToReference(theta, _Prompts(case), outs, seen, _LOGIT_TOL)
  kv = eng.Stats()["kv_pages"]
  assert (kv["window_pages_released"] > 0) == (
      max(_PROMPTS[case]) > _WINDOW + 16)
  assert kv["kinds"]["window"]["in_use"] == kv["kinds"]["full"]["in_use"] == 0
  assert eng.Stats()["state_slots"]["in_use"] == 0


def test_bf16_weights_fail_the_tolerance(tiny):
  task, theta = tiny[8]
  rounded = jax.tree_util.tree_map(
      lambda x: x.astype(jnp.bfloat16).astype(x.dtype), theta)
  prompt = _Prompts("shorter_than_one")[0]
  _, seen, outs = _Serve(task, rounded, [prompt], 2)
  seq = np.concatenate([prompt, np.asarray(outs[0], np.int32)])
  diff = np.abs(seen[0, 9] - _ReferenceLogits(theta, seq, 9)).max()
  assert diff > 10 * _LOGIT_TOL, diff


def test_bf16_serving_stays_inside_its_tolerance(tiny):
  """The program as the benchmark runs it (bf16 weights and activations, f32
  scan state) against the f32 reference of the same bf16 weights."""
  task = _Task(8, jnp.bfloat16)
  theta = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), tiny[8][1])
  prompts = _Prompts("uneven_chunks_in_one_step")
  _, seen, outs = _Serve(task, theta, prompts, 8)
  _HoldToReference(theta, prompts, outs, seen, _BF16_TOL)


def test_a_reused_slot_starts_from_zero_state(tiny):
  """Two requests through the one slot, one after the other: the second
  reads nothing the first left in the scan state or the convolution tail."""
  task, theta = tiny[8]
  a, b = _Prompts("uneven_chunks_in_one_step")[1:]
  eng = _Engine(task, theta, 1)
  probe = _Probe(eng, task)
  first = eng.Submit(a, 4)
  while not first.done:
    eng.StepOnce()
  assert float(jnp.abs(eng._states.blocks[0][0].scan).max()) > 0
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
  task, theta = tiny[8]

  def _Drop(eng, step):
    if step == 4:                                 # 80 of 90 tokens are in
      for block in eng._states.blocks:
        for layer in block:
          if leaf in layer:
            layer[leaf] = jnp.zeros_like(layer[leaf])

  prompts = _Prompts("longer_than_three_windows")
  _, seen, outs = _Serve(task, theta, prompts, 2, between=_Drop)
  seq = np.concatenate([prompts[0], np.asarray(outs[0], np.int32)])
  diff = np.abs(seen[0, 89] - _ReferenceLogits(theta, seq, 89)).max()
  assert diff > 40 * _LOGIT_TOL, diff


def test_slot_state_survives_a_spill_and_a_restore(tiny):
  """Mid-prompt, the slot's state rows go to the host (the engine's slot
  gather), the device's are overwritten, and come back into ANOTHER value
  of the same slot (the engine's slot scatter): the stream is unchanged."""
  task, theta = tiny[8]

  def _SpillRestore(eng, step):
    if step == 2:
      rows = eng._SpillStateRow(0)
      assert sorted(r.shape for r in rows) == sorted(
          [(2, 3, 96), (2, 8, 96), (1, 3, 96), (1, 8, 96)])
      for block in eng._states.blocks:
        for layer in block:
          for name in layer:
            layer[name] = jnp.full_like(layer[name], 7.0)
      eng._RestoreStateRow(0, rows)

  prompts = _Prompts("longer_than_three_windows")
  _, seen, outs = _Serve(task, theta, prompts, 4, between=_SpillRestore)
  _HoldToReference(theta, prompts, outs, seen, _LOGIT_TOL)


@pytest.mark.parametrize("case", ["longer_than_three_windows",
                                  "uneven_chunks_in_one_step"])
def test_no_layer_reads_a_page_it_may_not(tiny, served, case):
  """With NaN, after every step, in every page that no live row holds (what
  a window of 3 pages left behind, and everything but the full layer's own
  pages for the layers that own none), the engine streams the same tokens
  and its logits stay finite."""
  task, theta = tiny[12]
  _, _, clean = served(12, case)
  _, seen, outs = _Serve(task, theta, _Prompts(case), 8, poison=True)
  assert outs == clean
  assert all(np.isfinite(v).all() for v in seen.values())


def test_cross_layers_read_the_full_layers_pages(tiny):
  """The layers that own no pages follow the full layer's block table and no
  other: with the window layers' tables (not their pages) pointed at the
  trash page for the last step, only what the window layers compute moves;
  with the full layer's table pointed there, the cross layers move too."""
  task, theta = tiny[8]
  prompt = _Prompts("shorter_than_one")[0]
  eng = _Engine(task, theta, 1)
  handle = eng.Submit(prompt, 8)
  for _ in range(3):
    eng.StepOnce()
  states, tables = eng._states, np.array(eng._kind_pages.tables)
  assert tables.shape[0] == 3                     # two window layers, one full
  rows = ragged_lib.BuildRaggedRows(np.array([1]), np.array([11]), 17, 16)
  rows = ragged_lib.RaggedRows(*(jnp.asarray(m) for m in rows))
  ids = jnp.zeros((1, 17), jnp.int32).at[0, 0].set(5)

  def _CrossInput(tables):
    """What the last layer (cross) adds to the stream, by difference: the
    model's hidden with and without its output projection."""
    logits, _ = task.RaggedStep(theta, ids, states, jnp.asarray(tables), rows)
    return np.asarray(logits[0, 0])

  base = _CrossInput(tables)
  trash = eng.alloc.num_pages
  full_gone = tables.copy()
  full_gone[2] = trash
  assert np.abs(_CrossInput(full_gone) - base).max() > 1e-3
  # a stack whose cross layer read a window layer's table instead would not
  # notice the full layer's going: hold that with the layer's own weights off
  silent = jax.tree_util.tree_map(lambda x: x, theta)
  last = silent.stack.block_4.x_layers[0].atten
  last.w_post = jnp.zeros_like(last.w_post)
  full_off = silent.stack.block_2.x_layers[0].atten
  full_off.w_post = jnp.zeros_like(full_off.w_post)

  def _Logits(th, tb):
    return np.asarray(task.RaggedStep(th, ids, states, jnp.asarray(tb),
                                      rows)[0][0, 0])

  # with both attention layers that use the full layer's table silenced,
  # that table no longer matters: nothing else reads it
  np.testing.assert_allclose(_Logits(silent, full_gone),
                             _Logits(silent, tables), atol=1e-6)
  handle.Cancel()


def test_one_pool_nine_tables_and_slot_state(tiny):
  task, theta = tiny[12]
  eng = _Engine(task, theta, 2)
  kp = eng._kind_pages
  # three window layers and the full one own pages; two cross layers do not
  assert kp.windows == (_WINDOW,) * 3 + (0,) and kp.alloc is eng.alloc
  assert eng.alloc.num_pages == 48 * 4
  assert task.stack.SharedKvReadLayers() == 2
  pools = [tuple(x.shape) for x in jax.tree_util.tree_leaves(eng._states)
           if x.ndim == 4 and x.shape[1] == 8]
  assert pools == [(48 * 4 + 1, 8, 4, 8)] * 2            # K and V, once
  # scan state and convolution tail, a slot and a Mamba layer
  assert eng._states.blocks[0][0].scan.shape == (3, 2, 8, 96)
  assert eng._states.blocks[1][0].conv.shape == (1, 2, 3, 96)
  assert eng.mixers["num_ssm"] == 4
  assert eng.Stats()["state_slots"]["bytes_per_slot"] == 4 * 4 * 96 * 11
  # a request's pages: min(its pages, the window's cap) a window layer, all
  # of them for the full layer, none for a layer that owns none
  eng.Submit(np.arange(1, 101, dtype=np.int32), 4)
  eng.StepOnce()
  assert eng.Stats()["kv_pages"]["in_use"] == 3 * kp.caps[0] + 13


@pytest.mark.parametrize("kw,names", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(spec=spec_decode.SelfDraft(k=2, num_layers=2)), "spec"),
    (dict(scheduler_mode="priority"), "priority"),
])
def test_paths_of_one_block_table_refuse_the_stack(tiny, kw, names):
  task, theta = tiny[8]
  with pytest.raises(ValueError, match=names):
    _Engine(task, theta, 2, **kw)


def test_engine_counts_the_scan_and_the_unread_tokens(tiny, served):
  eng, _, outs = served(8, "uneven_chunks_in_one_step")
  st = eng.Stats()
  prompt_tokens = sum(_PROMPTS["uneven_chunks_in_one_step"])
  streamed = sum(len(o) for o in outs)
  # every prompt token and every token fed back went through the scan once
  assert st["ssm_tokens"] == prompt_tokens + streamed - 3
  # of a prompt only its last token is read by a sampler
  assert st["cross_tokens_unread"] == prompt_tokens - 3
  assert st["shared_kv_read_layers"] == 1
  assert (st["state_slots"]["in_use"], st["state_slots"]["peak_in_use"]) == (
      0, 3)
  records = [r for r in eng.trace.Steps() if r.counters]
  assert {"ssm_tokens", "cross_tokens_unread"} <= set(records[-1].counters)


def test_int8_pages_are_refused(tiny):
  task, theta = tiny[8]
  with pytest.raises((NotImplementedError, AssertionError)):
    _Engine(task, theta, 2, kv_cache_dtype="int8")
