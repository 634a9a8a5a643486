"""What a serving step costs to TRACE (PERF.md section 6, PRs 49 and 51).

The step program is traced in every process, set-up is judged in every cell,
and every `jnp` call on a tracer is a trace of its own: counts are exact on a
CPU, times are not asserted.

- `ops/power_retention.BuildStepPlan`, written in `jax.lax` over constants of
  numpy, is field by field what its numpy twin gives over seeded packs, and
  traces in one event,
- `core/ragged.BuildTokenView` and `ops/ragged_block_attend.BuildAttendPlan`
  trace in one event,
- the packed convolution of the Mamba layers (`core/ssm._PackedConv`,
  `_FreshTail`, `_PackedConvTail`) against a token-by-token numpy twin, and
  on prefixes of the packed axis (what `ragged.OverLiveRows` runs it over);
  its jaxpr gathers no array of T rows from the slots' tails (PR 66),
- each tiny stack's whole first step stays under a ceiling of trace events
  that stands beside what the tree before the step's conditionals traced
  (core/ragged.OverLiveRows traces a row-wise block once a width),
- `ragged.OverLiveRows` with no plan is its function and nothing else.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.core import retention as retention_lib
from lingvo_tpu.core import ssm
from lingvo_tpu.ops import power_retention as op
from lingvo_tpu.serving import engine as engine_lib

from tests.test_head_cols import _FAMILIES


class _Traces:
  """The `jaxpr_trace_duration` events JAX reports inside the block."""

  def __enter__(self):
    self.events = []
    monitoring.register_event_duration_secs_listener(self._On)
    return self

  def __exit__(self, *exc):
    monitoring.unregister_event_duration_listener(self._On)

  def _On(self, event, secs, **kw):
    del secs
    if event.endswith("/jaxpr_trace_duration"):
      self.events.append(kw.get("fun_name"))


# -- the retention step plan ---------------------------------------------------


def _NumpyStepPlan(rows, b, page, bq, reset=True):
  """`BuildStepPlan` in numpy: the lists as `searchsorted` and `nonzero` say
  them."""
  i32 = np.int32
  t = rows.row_of.shape[0]
  nb, span, ne = op.PlanSizes(b, t, page, rows.row_cols.shape[1], bq)
  p0, ln = rows.row_q_pos.astype(i32), rows.row_len.astype(i32)
  live = ln > 0
  fresh = live & (p0 == 0) & reset
  j0 = p0 // page
  start = np.clip(rows.row_cols[:, 0].astype(i32), 0, t - 1)
  row = np.clip(rows.row_of.astype(i32), 0, b - 1)
  pos = rows.pos.astype(i32)
  jj = np.clip(pos // page - j0[row], 0, span - 1)
  off = np.where(rows.valid, pos % page, np.arange(t, dtype=i32) % page)
  col = np.clip(rows.col_of.astype(i32), 0, None)
  folds = np.where(live, (p0 + ln) // page - j0, 0)
  nblk = (ln + bq - 1) // bq
  cum = np.cumsum(nblk)
  i = np.arange(nb, dtype=i32)
  blk_row = np.clip(np.searchsorted(cum, i, side="right"), 0, b - 1)
  k = i - (cum - nblk)[blk_row]
  blk_live = i < cum[-1]
  blk_n = np.where(blk_live, np.clip(ln[blk_row] - k * bq, 0, bq), 0)
  blk_first = np.where(blk_live, start[blk_row] + k * bq, 0)
  blk_pages = np.where(
      blk_live, (p0[blk_row] + k * bq + blk_n - 1) // page - j0[blk_row] + 1,
      0)
  tok_at = np.where(rows.valid,
                    ((cum - nblk)[row] + col // bq) * bq + col % bq, 0)
  pcum = np.cumsum(blk_pages)
  m = np.arange(nb * span, dtype=i32)
  pair_blk = np.clip(np.searchsorted(pcum, m, side="right"), 0, nb - 1)
  pair_jj = np.clip(m - (pcum - blk_pages)[pair_blk], 0, span - 1)
  reads = live & ~fresh
  wide = blk_live & (reads & (ln > 1))[blk_row]
  sblk = np.zeros((nb,), i32)
  sblk[:wide.sum()] = np.flatnonzero(wide)
  cnt = folds + (fresh & (folds == 0))
  ecum = np.cumsum(cnt)
  e = np.arange(ne, dtype=i32)
  e_row = np.clip(np.searchsorted(ecum, e, side="right"), 0, b - 1)
  e_jj = np.clip(e - (ecum - cnt)[e_row], 0, span - 1)
  return op.StepPlan(
      row=row, jj=jj, off=off, j0=j0, off0=p0 % page, start=start, live=live,
      fresh=fresh, folds=folds, blk_row=blk_row, blk_first=blk_first,
      blk_n=blk_n, tok_at=tok_at, pair_blk=pair_blk, pair_jj=pair_jj,
      pairs=pcum[-1], sblk=sblk, sblks=wide.sum(),
      decode=reads & (ln == 1), e_row=e_row, e_jj=e_jj,
      e_zero=fresh[e_row] & (e_jj == 0), e_add=folds[e_row] > e_jj,
      e_cnt=cnt, entries=ecum[-1])


# (row_len, row_q_pos) of packs of 6 rows, 40 columns, rows of up to 32,
# pages of 8: what a step's mix can be
_PACKS = {
    "decode_only": ([1, 1, 0, 1, 1, 1], [21, 6, 0, 15, 8, 40]),
    "chunk_beside_decode_rows": ([1, 26, 1, 0, 1, 0], [21, 16, 7, 0, 9, 3]),
    "fresh_rows": ([3, 1, 9, 0, 1, 5], [0, 12, 0, 0, 0, 0]),
    "one_page_folded": ([1, 5, 2, 1, 0, 8], [7, 3, 14, 30, 0, 8]),
    "several_pages_folded": ([32, 1, 0, 0, 7, 0], [5, 15, 0, 0, 1, 0]),
    "empty": ([0] * 6, [0, 3, 9, 0, 1, 2]),
    "full": ([32, 1, 1, 4, 1, 1], [3, 11, 0, 6, 7, 0]),
}


@pytest.mark.parametrize("bq", [4, 64])
@pytest.mark.parametrize("pack", list(_PACKS))
def test_the_lax_step_plan_is_its_numpy_twin(pack, bq):
  lens, p0 = _PACKS[pack]
  rows = ragged_lib.BuildRaggedRows(lens, p0, 40, 32)
  for reset in (True, False):
    got = jax.jit(lambda r: op.BuildStepPlan(r, 6, 8, bq, reset=reset))(
        jax.tree_util.tree_map(jnp.asarray, rows))
    want = _NumpyStepPlan(rows, 6, 8, bq, reset=reset)
    for name in op.StepPlan._fields:
      a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
      assert a.shape == b.shape and a.dtype.kind == b.dtype.kind, name
      assert a.dtype.itemsize == (1 if a.dtype.kind == "b" else 4), name
      np.testing.assert_array_equal(a, b, err_msg=f"{name} reset={reset}")


def test_seeded_packs_give_the_twins_plan():
  rng = np.random.default_rng(51)
  build = jax.jit(lambda r: op.BuildStepPlan(r, 5, 8, 4))
  for _ in range(40):
    lens = rng.integers(0, 17, 5) * (rng.random(5) < 0.8)
    while lens.sum() > 24:
      lens[np.argmax(lens)] //= 2
    p0 = rng.integers(0, 50, 5) * (rng.random(5) < 0.7)
    rows = ragged_lib.BuildRaggedRows(lens, p0, 24, 16)
    got = build(jax.tree_util.tree_map(jnp.asarray, rows))
    want = _NumpyStepPlan(rows, 5, 8, 4)
    for name in op.StepPlan._fields:
      np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                    np.asarray(getattr(want, name)),
                                    err_msg=f"{name} {lens} {p0}")


def test_the_step_plan_traces_in_one_event():
  """At longwrite's shapes (16 rows, 528 columns, rows of up to 512, pages of
  128): the `jnp` plan was 118 to 182 events by what the process had traced
  before; `lax` primitives are none, so the count is the `jit`'s own."""
  rows = jax.tree_util.tree_map(jnp.asarray, ragged_lib.BuildRaggedRows(
      [1] * 16, list(range(0, 160, 10)), 528, 512))
  with _Traces() as traces:
    jax.jit(lambda r: op.BuildStepPlan(r, 16, 128)).lower(rows)
  assert len(traces.events) == 1, traces.events     # ISSUE 51 asked: under 20


# -- the attention layers' plan --------------------------------------------------


@pytest.mark.parametrize("window,lanes,bq,tree", [
    (0, 1, 16, False), (0, 1, 16, True), (24, 8, 64, True)])
def test_the_token_view_and_the_attend_plan_trace_in_one_event(window, lanes,
                                                               bq, tree):
  """At docs' shapes (32 rows, 544 columns, pages of 128). Their values are
  held to a numpy reference by tests/test_attend_plan.py; through `jnp` they
  were 23 and 71 events."""
  from lingvo_tpu.ops import ragged_block_attend as rba
  rows = jax.tree_util.tree_map(jnp.asarray, ragged_lib.BuildRaggedRows(
      [1] * 31 + [200], list(range(0, 320, 10)), 544, 512))
  key = rba.PlanKey(128, window, bq, lanes, tree, True)

  def _Plan(rows):
    tok = ragged_lib.BuildTokenView(rows, 32, 20, 128)
    tree_args = (tok.q_start, rows.anc_lo, rows.anc_hi) if tree else ()
    return rba.BuildAttendPlan(key, tok.row, tok.q_end, *tree_args, b=32,
                               t_pages=20)

  with _Traces() as traces:
    jax.jit(_Plan).lower(rows)
  assert len(traces.events) == 1, traces.events


# -- the Mamba layers' packed convolution --------------------------------------


def _NumpyConv(u, held, w, rows):
  """-> (the convolution's sum [T, C], every row's tail after the step
  [B, K - 1, C]), token by token."""
  k = w.shape[0]
  tail = np.where((rows.row_q_pos == 0)[:, None, None], 0.0, held)
  conv = np.zeros_like(u)
  new_tail = tail.copy()
  for r, n in enumerate(rows.row_len):
    if not n:
      continue
    cols = rows.row_cols[r, :n]
    seq = np.concatenate([tail[r], u[cols]], axis=0)    # [K - 1 + n, C]
    for j, c in enumerate(cols):
      # taps in the order the layer adds them: the token's own first
      conv[c] = w[k - 1] * u[c]
      for back in range(1, k):
        conv[c] += w[k - 1 - back] * seq[k - 1 + j - back]
    new_tail[r] = seq[-(k - 1):]
  return conv, new_tail


# the packs above and, added to them, what the two parts of the sum meet at
# their edges (6 rows, 40 columns)
_CONV_PACKS = {
    **_PACKS,
    "a_row_shorter_than_the_tail": ([2, 1, 2, 0, 1, 2], [5, 9, 0, 0, 14, 3]),
    "a_request_starts_beside_a_chunk_that_continues": (
        [12, 12, 1, 0, 0, 1], [0, 24, 7, 0, 0, 0]),
    "every_token_reads_a_tail": ([1] * 6, [4, 0, 9, 1, 30, 2]),
    "padding_behind_the_live_tokens": ([3, 0, 2, 0, 0, 1],
                                       [8, 0, 0, 5, 0, 11]),
    "a_cut_that_splits_a_rows_head": ([2, 3, 9, 1, 0, 4],
                                      [6, 2, 0, 17, 0, 3]),
}
# prefixes of the packed axis (W of `ragged.OverLiveRows`): 3 ends after the
# first token of a row that has more, 6 is the decode-only step's (a token a
# slot), 16 splits the chunks
_CUTS = (3, 6, 16)


@pytest.mark.parametrize("k", [2, 3, 4])
@pytest.mark.parametrize("pack", list(_CONV_PACKS))
def test_the_packed_convolution_is_its_numpy_twin(pack, k):
  rng = np.random.default_rng(k)
  fn = jax.jit(lambda u, held, w, rows: (
      ssm._PackedConv(u, held, w, rows),
      ssm._PackedConvTail(u, ssm._FreshTail(held, rows), rows)))
  lens, p0 = _CONV_PACKS[pack]
  rows = ragged_lib.BuildRaggedRows(lens, p0, 40, 32)
  u = rng.normal(size=(40, 6)).astype(np.float32)
  held = rng.normal(size=(6, k - 1, 6)).astype(np.float32)
  # a slot the step does not hold keeps anything: nothing of it reaches a
  # token, and its tail stays what it was
  held[np.asarray(lens) == 0] = np.nan
  w = rng.normal(size=(k, 6)).astype(np.float32)
  conv, tail = fn(u, held, w, jax.tree_util.tree_map(jnp.asarray, rows))
  want_conv, want_tail = _NumpyConv(u, held, w, rows)
  live = np.flatnonzero(rows.valid)
  np.testing.assert_allclose(np.asarray(conv)[live], want_conv[live],
                             rtol=1e-5, atol=1e-5)
  assert np.isfinite(np.asarray(conv)).all()      # the padding tokens' too
  np.testing.assert_array_equal(np.asarray(tail), want_tail)
  # a prefix of the packed axis gives that prefix of the sum
  # (ragged.OverLiveRows runs it over the first W rows): the per-row fields
  # whole, a tail's place beyond the prefix dropped
  for cut in _CUTS:
    cut_rows = rows._replace(row_of=jnp.asarray(rows.row_of[:cut]),
                             col_of=jnp.asarray(rows.col_of[:cut]))
    got = ssm._PackedConv(jnp.asarray(u[:cut]), jnp.asarray(held),
                          jnp.asarray(w), cut_rows)
    assert got.shape == (cut, 6) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(conv)[:cut],
                               rtol=1e-5, atol=1e-5, err_msg=f"W = {cut}")


def _KernelProbe():
  """tools/kernel_probe.py as a module."""
  import importlib.util
  import os
  spec = importlib.util.spec_from_file_location("kernel_probe", os.path.join(
      os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools",
      "kernel_probe.py"))
  kernel_probe = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(kernel_probe)
  return kernel_probe


@pytest.mark.parametrize("k,slots", [(4, 8), (3, 16), (2, 8)])
def test_the_packed_convolution_gathers_no_array_of_t_rows(k, slots):
  """The mechanism of PR 66, on a CPU: only a row's first K - 1 tokens read
  its slot's tail, so the sum at [T, C] builds no [T, C] array out of the
  tails by a gather (the form it replaced gathered one a tap: the probe
  keeps it, tools/kernel_probe.py `_ConvLoop`, and it is counted here beside
  it, by the probe's own count); it stays f32 throughout."""
  kernel_probe = _KernelProbe()
  t, c = 72, 24
  rows = jax.tree_util.tree_map(jnp.asarray, ragged_lib.BuildRaggedRows(
      [1] * (slots - 1) + [40], list(range(3, 3 + slots)), t, 48))
  operands = (jnp.zeros((t, c)), jnp.zeros((slots, k - 1, c)),
              jnp.zeros((k, c), jnp.bfloat16), rows)
  jaxpr = jax.make_jaxpr(ssm._PackedConv)(*operands)
  assert kernel_probe._TokenRowGathers(jaxpr.jaxpr, t) == 0
  assert [v.aval.dtype for v in jaxpr.jaxpr.outvars] == [jnp.float32]
  assert jaxpr.out_avals[0].shape == (t, c)
  floats = {v.aval.dtype for e in jaxpr.jaxpr.eqns for v in e.outvars
            if jnp.issubdtype(v.aval.dtype, jnp.floating)
            and v.aval.shape[-1:] == (c,) and v.aval.ndim > 1}
  assert floats == {jnp.dtype(jnp.float32)}, floats
  before = jax.make_jaxpr(
      lambda *ops: kernel_probe._ConvLoop(ssm, *ops))(*operands)
  assert kernel_probe._TokenRowGathers(before.jaxpr, t) == k - 1


def test_the_kernel_probe_runs_the_packed_convolution(capsys):
  """tools/kernel_probe.py --case packed_conv at the CPU's rehearsal sizes:
  one layer's scope through the form PR 66 replaced, the tree's and a form
  that lost, each held to the first; counts, never a time."""
  import json
  kernel_probe = _KernelProbe()
  assert kernel_probe.main(["--case", "packed_conv", "--tiny", "--calls", "1",
                            "--shapes", "granite,lfm2", "--variants",
                            "loop,tree,scatter_add"]) == 0
  lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
           if l.startswith("{")]
  assert [(l["shape"], l["step"], l["variant"]) for l in lines] == [
      (shape, step, variant) for shape in ("granite", "lfm2")
      for step in ("decode", "chunk")
      for variant in ("loop", "tree", "scatter_add")]
  for l in lines:
    assert l["tiny"] and l["device"]["platform"] == "cpu"
    assert l["ms_a_layer"] is None
    k = l["k"]
    assert (k, l["slots"]) == ((4, 4) if l["shape"] == "granite" else (3, 4))
    assert l["token_row_gathers"] == (k - 1 if l["variant"] == "loop" else 0)
    # decode: a token a slot; chunk: three such rows beside a chunk of 24
    assert l["conv_tail_tokens"] == (4 if l["step"] == "decode"
                                     else 3 + (k - 1))
    if l["variant"] != "loop":
      assert l["within_1e-5"] and l["tail_equal_first"]


# -- a whole step --------------------------------------------------------------

# trace events of a tiny engine's FIRST step (f32, the engine below) at the
# PARENT of PR 51 (`6246f70`, JAX 0.9.0), read in a process that had traced
# nothing before, where the count is largest (a `jnp` function already traced
# at the same shapes is a cache hit and no event), and the allowance this tree
# is held to over it. The row-wise blocks trace twice (ragged.OverLiveRows,
# once a width); what the plans in `jax.lax` gave back pays for that within
# 12% in every stack but Phi-4-flash's, the exception: five conditionals a
# block (two a Mamba-1 layer, whose packed convolution in `jnp` traces once a
# width, two a differential-attention layer, one a gated memory unit) read
# 1,084 for 778 (+39%) and are allowed 40%. Events are not seconds: on the
# benchmark's host the same convolution in `jax.lax` (59 events a body fewer)
# traced Phi-4-flash's real step no faster (4.92 / 5.05 s against 4.77) and
# went back to `jnp` (PERF.md section 6, PR 51); the ceiling keeps a step
# from growing by a plan's worth of `jnp` calls unnoticed, nothing finer. A
# JAX that counts its own traces otherwise moves both sides: take the
# parent's count again with `_FirstStepTraces` before moving a number.
_STEP_TRACES = {
    "dense": (244, 0.12),
    "smallthinker": (865, 0.12),
    "phi4flash": (778, 0.40),
    "nemotron_h": (1100, 0.12),
    "brumby": (472, 0.12),
}


def _FirstStepTraces(family):
  task, theta = _FAMILIES[family](jnp.float32)
  eng = engine_lib.ServingLoop(
      task, theta, page_size=8, num_pages=48, max_batch=4, max_seq_len=128,
      prefill_token_budget=8)
  eng.Submit([5, 9, 2], 8, eos_id=None, seed=11)
  with _Traces() as traces:
    eng.StepOnce()
  assert eng.Stats()["compile"]["step_programs"] == 1
  return len(traces.events)


@pytest.mark.parametrize("family", list(_FAMILIES))
def test_a_whole_step_stays_under_its_ceiling_of_trace_events(family):
  before, allowance = _STEP_TRACES[family]
  got = _FirstStepTraces(family)
  assert 0 < got <= int(before * (1.0 + allowance)), (got, before, allowance)


# -- no plan, no conditional ---------------------------------------------------


def test_over_live_rows_without_a_plan_is_its_function():
  calls = []

  def _Fn(x, y):
    calls.append((x.shape, y.shape))
    return x + 1.0, (y * 2.0,)

  x, y = jnp.ones((1, 12, 3)), jnp.ones((1, 12, 2, 2))
  for plan in (None, retention_lib.RetentionPlan(None, None)):  # no width in the plan
    calls.clear()
    jaxpr = jax.make_jaxpr(
        lambda x, y: ragged_lib.OverLiveRows(_Fn, plan, x, y))(x, y)
    assert calls == [((1, 12, 3), (1, 12, 2, 2))]
    assert [e.primitive.name for e in jaxpr.eqns] == ["add", "mul"]
  # with one, both widths are traced and one conditional chooses
  rows = ragged_lib.BuildRaggedRows([1, 2, 0, 0], [4, 0, 0, 0], 12, 8)
  width = ragged_lib.BuildLiveWidth(jax.tree_util.tree_map(jnp.asarray, rows))
  assert width.rows == 4 and bool(width.fits)
  plan = retention_lib.RetentionPlan(None, width)
  calls.clear()
  out, (out2,) = ragged_lib.OverLiveRows(_Fn, plan, x, y)
  assert sorted(calls) == [((1, 4, 3), (1, 4, 2, 2)), ((1, 12, 3), (1, 12, 2, 2))]
  np.testing.assert_array_equal(np.asarray(out[0, :4]), 2.0)
  np.testing.assert_array_equal(np.asarray(out[0, 4:]), 0.0)   # behind: zeros
  np.testing.assert_array_equal(np.asarray(out2[0, :4]), 2.0)
