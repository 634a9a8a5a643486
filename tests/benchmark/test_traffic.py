"""The traffic generator: a seed permutes, it does not resample."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import traffic as t

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "traffic")
SEEDS = (1, 7, 3000000019)      # the driver's seeds pass 2**31


def _Mix(name):
  with open(os.path.join(_DIR, name + ".json")) as f:
    return json.load(f)


def _Pairs(reqs, sampled):
  return sorted((r.prompt_len, r.new_tokens) for r in reqs
                if r.sampled == sampled)


@pytest.mark.parametrize("mix,max_batch", [("chat", 32), ("docs", 32)])
def test_same_multiset_for_every_seed_in_another_order(mix, max_batch):
  tr = _Mix(mix)
  runs = [t.Generate(tr, 30, s, max_batch) for s in SEEDS]
  for sampled in (True, False):
    want = _Pairs(runs[0], sampled)
    assert want, "an empty phase proves nothing"
    for reqs in runs[1:]:
      assert _Pairs(reqs, sampled) == want
  orders = {tuple((r.prompt_len, r.new_tokens) for r in reqs) for reqs in runs}
  # a mix with shuffle_block 1 keeps one order: its seeds draw ids only
  assert len(orders) == (1 if tr.get("shuffle_block") == 1 else len(SEEDS))
  assert len({json.dumps(t.TotalWork(r)) for r in runs}) == 1


@pytest.mark.parametrize("seconds,rate", [
    (10, None), (30, None), (51, None),       # the rate chat.json states
    (30, 1.6), (30, 6.5), (30, 8.8), (30, 12.3)])   # and others a sweep tries
def test_open_loop_arrival_count_is_exact(seconds, rate):
  tr = _Mix("chat")
  if rate is not None:
    tr = dict(tr, rate_per_s=rate)
  rate, lead = tr["rate_per_s"], tr["lead_in_s"]
  for seed in SEEDS:
    reqs = t.Generate(tr, seconds, seed)
    window = [r for r in reqs if r.sampled]
    lead_in = [r for r in reqs if not r.sampled]
    assert len(window) == round(rate * seconds)
    assert len(lead_in) == round(rate * lead)
    assert all(0 <= r.due_s < lead for r in lead_in)
    assert all(lead <= r.due_s < lead + seconds for r in window)
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues)
    assert [r.index for r in reqs] == list(range(len(reqs)))


def test_arrival_times_differ_by_seed_and_repeat_for_one_seed():
  tr = _Mix("chat")
  a, b, c = (t.Generate(tr, 30, s) for s in (5, 5, 6))
  assert [r.due_s for r in a] == [r.due_s for r in b]
  assert [r.due_s for r in a] != [r.due_s for r in c]


def test_lengths_stay_inside_the_stated_clips():
  for mix in ("chat", "docs"):
    tr = _Mix(mix)
    reqs = [r for r in t.Generate(tr, 30, 3, 32) if r.sampled]
    p, n = tr["prompt_len"], tr["new_tokens"]
    assert min(r.prompt_len for r in reqs) >= p["min"]
    assert max(r.prompt_len for r in reqs) <= p["max"]
    assert min(r.new_tokens for r in reqs) >= n["min"]
    assert max(r.new_tokens for r in reqs) <= n["max"]


def test_quantile_grid_has_the_stated_median():
  grid = t.QuantileGrid({"dist": "lognormal", "median": 256, "sigma": 1.0,
                         "min": 1, "max": 10**6}, 1001)
  assert grid == sorted(grid)
  assert abs(grid[500] - 256) <= 1
  assert t.QuantileGrid({"dist": "uniform", "min": 32, "max": 128}, 4) == [
      44, 68, 92, 116]
  assert t.QuantileGrid({"dist": "fixed", "value": 9}, 3) == [9, 9, 9]
  with pytest.raises(ValueError):
    t.QuantileGrid({"dist": "zipf"}, 3)


def test_closed_loop_has_its_clients_and_a_window_sized_list():
  tr = _Mix("docs")
  reqs = t.Generate(tr, 30, 11, max_batch=32)
  clients = t.NumClients(tr, 32)
  assert clients == 64
  assert sum(not r.sampled for r in reqs) == clients
  assert "lead_in_s" not in tr, "the window opens at a finish, not a second"
  assert len(reqs) == t.ClosedLoopList(tr, 30, 32) == (
      2 * clients + t.WindowRequests(tr, 30))
  assert t.WindowRequests(tr, 30) == round(tr["requests_per_s_hint"] * 30)
  assert all(r.due_s is None for r in reqs)


@pytest.mark.parametrize("mix,seconds,max_batch,want", [
    # the clients' first requests, the window's work, one in flight each
    ({"clients_per_slot": 2, "requests_per_s_hint": 5.0}, 30, 32,
     64 + 150 + 64),
    ({"clients_per_slot": 2, "requests_per_s_hint": 5.0}, 10, 32,
     64 + 50 + 64),
    ({"clients_per_slot": 2, "requests_per_s_hint": 5.0}, 2, 4, 8 + 10 + 8),
    ({"clients_per_slot": 1, "requests_per_s_hint": 0.3}, 30, 8, 8 + 9 + 8),
    ({"clients_per_slot": 1, "requests_per_s_hint": 0.01}, 30, 8, 8 + 1 + 8),
])
def test_a_closed_loop_s_list_is_sized_in_work(mix, seconds, max_batch, want):
  assert t.ClosedLoopList(mix, seconds, max_batch) == want


def test_a_closed_loop_cuts_no_request_short():
  """Every request a closed loop sends, the clients' first ones too, has
  the lengths the mix states: the window serves the traffic its `why`
  names."""
  tr = _Mix("docs")
  p, n = tr["prompt_len"], tr["new_tokens"]
  reqs = t.Generate(tr, 30, SEEDS[2], 32)
  assert all(p["min"] <= r.prompt_len <= p["max"] for r in reqs)
  assert all(n["min"] <= r.new_tokens <= n["max"] for r in reqs)
  first = [(r.prompt_len, r.new_tokens) for r in reqs if not r.sampled]
  assert sorted(first) == sorted(t.PairMultiset(tr, 64))


@pytest.mark.parametrize("block", [1, 4, 1000])
def test_shuffle_block_is_the_mix_s_to_set(block):
  tr = dict(_Mix("docs"), shuffle_block=block)
  a, b = ([(r.prompt_len, r.new_tokens) for r in t.Generate(tr, 30, s, 32)
           if not r.sampled] for s in (1, 99))
  assert sorted(a) == sorted(b)
  assert (a == b) == (block == 1)
  if block == 4:   # inside blocks of four of one base order
    assert all(sorted(a[k:k + 4]) == sorted(b[k:k + 4])
               for k in range(0, 64, 4))


def test_prompt_ids_come_from_the_seed():
  tr = _Mix("chat")
  r = t.Generate(tr, 10, 3)[0]
  a = t.PromptIds(r, 3000000019, 32000)
  assert a.dtype == np.int32 and len(a) == r.prompt_len
  assert a.min() >= 1 and a.max() < 32000
  np.testing.assert_array_equal(a, t.PromptIds(r, 3000000019, 32000))
  assert not np.array_equal(a, t.PromptIds(r, 3000000020, 32000))


@pytest.mark.parametrize("seconds", [2, 10, 30, 51])
def test_docs_list_lasts_any_system_however_fast(seconds):
  """The window's work is fixed (the requests `seconds` hold at the rate the
  cell turns over today), so the clients draw at most their first requests,
  the window's, and one more each: the list holds exactly that, and a closed
  loop that never outruns its list never serves other requests than the
  list states (closed_loop_cycles 0; tests/benchmark/test_drive.py)."""
  tr = _Mix("docs")
  reqs = t.Generate(tr, seconds, SEEDS[0], 32)
  clients = t.NumClients(tr, 32)
  in_window = t.WindowRequests(tr, seconds)
  assert len(reqs) == clients + in_window + clients
