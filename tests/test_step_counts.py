"""Who counts a mixer's work (docs/serving_engine.md, "What the engine asks of
a stack"): a stack hands `ServingLoop` its counting functions once
(`StepCounts`, core/ragged.StackStepCounts) and the engine adds them up.

- the standing guard of PR 65: for every served family's tiny preset, the key
  set a step's trace record carries is the literal set the parent (`960b2eb`)
  carried for that stack (what `benchmarks/harness/*_cost.py` read through
  `spans.StepRecords`), and `_attend_bq` is the parent's value;
- no module under `lingvo_tpu/serving/` imports a module of `lingvo_tpu.ops`,
  and `engine.py` keeps none of the fields it counted a mixer's work by;
- the shared helper's rules, on stand-in mixers: layers that share a counting
  method are summed, a name is its first mixer's, the slot-state bytes are in
  the record beside a tail's rows alone, a plan is counted a distinct key, and
  the whole-page write shares one count of the step's runs with a layer that
  writes by runs.
"""

import ast
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

import lingvo_tpu
from lingvo_tpu.core import mla as mla_lib
from lingvo_tpu.core import ragged as ragged_lib
from lingvo_tpu.core import ssm
from lingvo_tpu.observe import schema as observe_schema
from lingvo_tpu.ops import diff_attend
from lingvo_tpu.ops import ragged_block_attend as rba
from lingvo_tpu.ops import run_write
from lingvo_tpu.serving import engine as engine_lib

from tests import test_head_cols

_MOE = {"moe_tokens_routed", "moe_expert_load_max", "moe_expert_load_mean",
        "moe_experts_active", "moe_pairs_elsewhere"}
_WINDOW = {"window_pages_released", "window_pages_allocated"}
_SLOTS = {"ssm_tokens", "cross_tokens_unread"}
# (`conv_tail_tokens`: PR 66's, beside the parent's names wherever a stack
# has a Mamba-2 or a gated short-convolution layer)
_SSD = {"ssd_state_rows", "ssd_narrow_rows", "conv_tail_tokens"}
_TAILS = {"conv_tail_rows", "slot_state_bytes", "conv_tail_tokens"}
_PAIRS = {"attend_live_pairs", "attend_clear_pairs", "attend_programs"}
# family -> (the keys of a step's record, `_attend_bq`) at the parent; with
# `+kernels` the attend kernels' lowering is forced, as on the chip
_PARENT_RECORD = {
    "dense": (set(), 8),
    "smallthinker": (_MOE | _WINDOW, 512),
    "phi4flash": (_SLOTS | _WINDOW, 512),
    "nemotron_h": (_MOE | _WINDOW | _SLOTS | _SSD, 512),
    "brumby": (_SLOTS | _WINDOW | {"retention_rows", "retention_folds",
                                   "retention_chunk_tokens"}, 0),
    "mistral4": (_MOE, 1024),
    "granite": (_MOE | _WINDOW | _SLOTS | _SSD, 512),
    "trinity": (_MOE | _WINDOW, 512),
    "lfm2": (_MOE | _WINDOW | _SLOTS | _TAILS, 512),
    "dense+kernels": (set(), 8),
    "mistral4+kernels": (_MOE | _PAIRS, 1024),
    "lfm2+kernels": (_MOE | _WINDOW | _SLOTS | _PAIRS | _TAILS, 512),
}
_FAMILIES = {
    **test_head_cols._FAMILIES, **test_head_cols._NEWER_FAMILIES,
    "granite": lambda dtype: test_head_cols._Registered(
        "lm.granite_hybrid.Granite40HSmallTiny", dtype)}


@pytest.mark.parametrize("case", list(_PARENT_RECORD))
def test_a_steps_record_carries_the_keys_the_parents_did(case, monkeypatch):
  family, _, kernels = case.partition("+")
  if kernels:
    monkeypatch.setattr(rba, "Lowering", lambda lowering: (
        "pallas" if lowering == "auto" else lowering))
    monkeypatch.setattr(mla_lib.MultiHeadLatentAttention, "_Lowering",
                        lambda self, page_size: "pallas")
  task, theta = _FAMILIES[family](jnp.float32)
  eng = engine_lib.ServingLoop(
      task, theta, page_size=8, num_pages=48, max_batch=4, max_seq_len=128,
      prefill_token_budget=8)
  eng.Submit(list(range(1, 12)), 2, eos_id=None, seed=3)
  eng.StepOnce()
  eng.StepOnce()
  keys, bq = _PARENT_RECORD[case]
  for record in eng.trace.Steps():
    # (a step that compiled names what: _StepSpans.End)
    assert set(record.counters or ()) - {"compile_fun_names"} == keys, (
        record.counters)
  assert eng._attend_bq == bq
  assert keys <= set(observe_schema.ENGINE_COUNTER_KEYS) | _WINDOW
  assert (eng.Stats()["attend_plans"] > 0) == bool(kernels)


def test_no_module_of_serving_imports_a_kernel_module():
  """The top layer reaches `ops/` through `core/` alone: a kernel's geometry
  is its mixer's word."""
  serving = pathlib.Path(lingvo_tpu.__file__).parent / "serving"
  files = sorted(serving.glob("**/*.py"))
  assert len(files) > 5
  for path in files:
    for node in ast.walk(ast.parse(path.read_text())):
      names = []
      if isinstance(node, ast.ImportFrom):
        names = [f"{node.module}.{a.name}" for a in node.names]
      elif isinstance(node, ast.Import):
        names = [a.name for a in node.names]
      assert not [n for n in names if n.startswith("lingvo_tpu.ops")], (
          path.name, names)
  text = (serving / "engine.py").read_text()
  for field in (
      "_attend_laid", "_attend_own", "_attend_rows", "_attend_plan_keys",
      "_attend_grid_pairs", "_attend_clear_keys", "_table_pages",
      "_kv_write_by_runs", "_kv_page_write_bound", "_retention_layers",
      "_ssd_layers", "_conv_tail_layers", "_slot_state_bytes_a_row"):
    assert field not in text, field
  assert "self._attend_bq = " in text


# -- the shared helper, on stand-in mixers -------------------------------------

_GEOMETRY = ragged_lib.StepGeometry(page_size=8, kv_cache_dtype=None,
                                    max_batch=4, tokens=12, table_pages=16)
# a decode row, a one-token prompt, a chunk over a page boundary, an empty slot
_Q_POS = np.array([9, 0, 6, 1], np.int64)
_LEN = np.array([1, 1, 5, 0], np.int64)


class _Rows:
  """A mixer with a slot state that counts (live rows x layers)."""

  def __init__(self, name="rows", slot_bytes=0):
    self._name, self._bytes = name, slot_bytes

  def StateBytesPerSlot(self):
    return self._bytes

  def StepCounts(self, geometry, layers):
    return [ragged_lib.StepCount(
        (self._name,), lambda q_pos, n: (layers * int((n > 0).sum()),), True)]


class _ByRuns:
  """An attention mixer: the block fill and the page write by runs."""

  def __init__(self, bq):
    self._bq = bq

  def StepCounts(self, geometry, layers):
    return [ragged_lib.BlockFillCount(self._bq),
            ragged_lib.RunWriteCount(geometry.page_size)]


class _Plain:
  """A mixer with a slot state and no counters of its own."""

  def StateBytesPerSlot(self):
    return 100


class _Stack:

  def __init__(self, mixers, keys=()):
    self._mixers, self._keys = mixers, list(keys)

  def MixerLayers(self):
    return self._mixers

  def RaggedPlanKeys(self, cached_states):
    return self._keys


def _Totals(counts):
  out = {}
  for c in counts:
    values = c.count(_Q_POS, _LEN)
    assert len(values) == len(c.names), c.names
    assert all(type(v) is int for v in values), (c.names, values)
    for k, v in zip(c.names, values):
      assert k in observe_schema.ENGINE_COUNTER_KEYS and k not in out, k
      out[k] = v
  return out


def test_layers_that_share_a_counting_method_are_summed():
  counts = ragged_lib.StackStepCounts(
      _Stack([(_Rows(), 2), (_Plain(), 7), (_Rows(), 3)]), None, _GEOMETRY)
  assert [c.names for c in counts] == [("rows",), ("slot_state_bytes",)]
  rows, slot = counts
  assert rows.count(_Q_POS, _LEN) == (5 * 3,) and rows.in_record
  # every mixer that keeps a slot state, read and written: not in the record
  assert slot.count(_Q_POS, _LEN) == (2 * 7 * 100 * 3,) and not slot.in_record


def test_slot_state_bytes_ride_the_record_beside_a_tails_rows():
  counts = ragged_lib.StackStepCounts(
      _Stack([(_Rows("conv_tail_rows", 40), 5), (_Plain(), 1)]), None,
      _GEOMETRY)
  assert _Totals(counts) == {"conv_tail_rows": 5 * 3,
                             "slot_state_bytes": 2 * (5 * 40 + 100) * 3}
  assert all(c.in_record for c in counts)


@pytest.mark.parametrize("kind,k,layers", [
    ("mamba2", 4, 9), ("mamba2", 2, 1), ("short_conv", 3, 7),
    ("short_conv", 4, 2)])
def test_the_tokens_that_read_a_tail_against_a_hand_count(kind, k, layers):
  """`conv_tail_tokens`: a row's first K - 1 tokens of the step read its
  slot's tail (`ssm._PackedConv`'s second part), times the layers. By hand
  over the pack above: the decode row and the one-token prompt one each, the
  chunk of five min(5, K - 1), the empty slot none."""
  if kind == "mamba2":
    p = ssm.Mamba2Layer.Params().Set(name="m", input_dim=16, num_heads=2,
                                     head_dim=8, state_dim=4, conv_width=k)
  else:
    p = ssm.ShortConvLayer.Params().Set(name="c", input_dim=16, conv_width=k)
  counts = p.Instantiate().StepCounts(_GEOMETRY, layers)
  totals = _Totals(counts)
  assert totals["conv_tail_tokens"] == layers * (1 + 1 + min(5, k - 1) + 0)
  # beside the names the layer fed before, which count what they counted
  rows = "ssd_state_rows" if kind == "mamba2" else "conv_tail_rows"
  assert totals[rows] == layers * 3
  assert all(c.in_record for c in counts)
  # over the live tokens: the share of the operand the tails' path reaches
  assert totals["conv_tail_tokens"] <= layers * int(_LEN.sum())


def test_a_name_is_its_first_mixers():

  class _Other(_ByRuns):
    def StepCounts(self, geometry, layers):
      return super().StepCounts(geometry, layers)[:1]

  first, second = _ByRuns(4), _Other(8)
  for mixers, bq in (([(first, 1), (second, 1)], 4),
                     ([(second, 1), (first, 1)], 8)):
    totals = _Totals(ragged_lib.StackStepCounts(_Stack(mixers), None,
                                                _GEOMETRY))
    # the chunk's five queries: two blocks of 4, one of 8
    assert totals["attend_query_blocks"] == 2 + (2 if bq == 4 else 1)
    assert totals["attend_block_queries"] == 7
    # the mixer that writes by runs is counted wherever it stands
    assert (totals["kv_write_runs"], totals["kv_write_tokens"]) == (4, 7)
    assert not any(k.startswith(("attend_live", "kv_page")) for k in totals)


@pytest.mark.parametrize("by_runs", [False, True])
def test_the_whole_page_write_shares_one_count_of_the_runs(by_runs,
                                                           monkeypatch):
  calls = []
  inner = run_write.RunCounts
  monkeypatch.setattr(run_write, "RunCounts", lambda *a, **kw: (
      calls.append(1), inner(*a, **kw))[1])
  mixers = [(_ByRuns(8), 2)] if by_runs else []
  totals = _Totals(ragged_lib.StackStepCounts(
      _Stack(mixers), None, _GEOMETRY, page_writes=True))
  assert len(calls) == 1
  assert totals["kv_page_writes"] == 4
  assert totals["kv_page_write_bound"] == diff_attend.PageWrites(4, 12, 8)
  assert (totals.get("kv_write_runs"), totals.get("kv_write_tokens")) == (
      (4, 7) if by_runs else (None, None))


def test_a_plan_is_counted_a_distinct_key_not_a_layer():
  full = rba.PlanKey(8, 0, 8, 1, True, True)
  window = full._replace(window=8, clear=True)
  twin = full._replace(kernel=False)
  counts = ragged_lib.StackStepCounts(
      _Stack([], [full, window, twin, full, window]), None, _GEOMETRY)
  assert [c.names for c in counts] == [
      ("attend_live_pairs", "attend_clear_pairs", "attend_programs"),
      ("attend_grid_pairs",)]
  assert counts[0].in_record and not counts[1].in_record
  totals = _Totals(counts)
  for i, name in enumerate(counts[0].names):
    assert totals[name] == sum(
        rba.PairCounts(k, _Q_POS, _LEN, 16)[i] for k in (full, window))
  assert totals["attend_grid_pairs"] == sum(
      rba.GridPairs(k, 4, 12, 16) for k in (full, window))
  # no key whose kernel reads `clear`: counted, and not in the record
  (pairs, _) = ragged_lib.StackStepCounts(_Stack([], [full]), None, _GEOMETRY)
  assert not pairs.in_record
