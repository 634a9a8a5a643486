"""How a run turns its window into one number."""

import math

import pytest

from benchmarks.harness import readings as r


def test_token_window_rate_counts_tokens_between_two_step_completions():
  # a step every 0.5 s, 100 tokens a step; the window cuts steps at both ends
  steps = [(0.5 * i, 100 * i) for i in range(1, 41)]
  rate, tokens, seconds = r.TokenWindowRate(steps, 2.2, 17.9)
  assert (tokens, seconds) == (100 * (35 - 5), 17.5 - 2.5)
  assert rate == pytest.approx(200.0)


def test_token_window_rate_counts_a_cut_request_for_what_it_got_done():
  # one long request is mid-flight at both edges: nothing of it "finished"
  # in the window, yet every token it did inside counts
  steps = [(1.0, 0), (2.0, 512), (3.0, 1024), (4.0, 1536), (5.0, 1537)]
  rate, tokens, _ = r.TokenWindowRate(steps, 1.5, 4.5)
  assert tokens == 1024 and rate == pytest.approx(512.0)


def test_token_window_rate_needs_two_completions():
  with pytest.raises(ValueError):
    r.TokenWindowRate([(1.0, 5)], 0.0, 2.0)


def _Flow(period=0.035, steps=1400, turn=6, burst=3, chunk=500, rows=14,
          shift=0.0):
  """A closed loop's flow since the clients' start: a step every `period`
  seconds; every `turn` steps a request finishes and the one admitted in its
  place brings `burst` steps of `chunk` prompt tokens beside the decode
  rows. `shift` moves every completion on the wall clock (the start fell
  later), which moves bursts across any edge a fixed second would cut."""
  out, tokens, finished = [], 0, 0
  for i in range(1, steps + 1):
    tokens += rows + (chunk if i % turn < burst else 0)
    finished += i % turn == 0
    out.append((shift + period * i, tokens, finished))
  return out


def test_finish_window_has_its_edges_at_finishes():
  steps = _Flow()
  win = r.FinishWindow(steps, opening=64, requests=140)
  by_time = {t: (n, f) for t, n, f in steps}
  assert by_time[win["t_open"]][1] == 64          # the 64th finish opens it
  n_close, f_close = by_time[win["t_close"]]
  assert f_close == 64 + 140 and win["finished"] == 140   # the 204th closes
  assert win["finished_at_open"] == 64
  assert win["tokens"] == n_close - by_time[win["t_open"]][0]
  assert win["seconds"] == pytest.approx(win["t_close"] - win["t_open"])
  assert win["seconds"] == pytest.approx(140 * 6 * 0.035)
  assert win["tok_s"] == pytest.approx(win["tokens"] / win["seconds"])
  # a whole number of the pool's turns: the rate of one turn, exactly
  assert win["tok_s"] == pytest.approx((6 * 14 + 3 * 500) / (6 * 0.035))


@pytest.mark.parametrize("shift", [0.0, 0.02, 0.06, 0.1, 0.17])
def test_a_burst_moved_across_a_wall_clock_edge_does_not_move_it(shift):
  """The start falls `shift` later: what two fixed seconds cut out of the
  flow changes by a burst or two, what lies between two finishes does not."""
  base = r.FinishWindow(_Flow(), 64, 140)
  moved = r.FinishWindow(_Flow(shift=shift), 64, 140)
  assert moved["tok_s"] == pytest.approx(base["tok_s"], rel=1e-9)
  assert moved["tokens"] == base["tokens"]
  by_seconds = [r.TokenWindowRate([x[:2] for x in _Flow(shift=s)], 17.5,
                                  47.5)[1] for s in (0.0, shift)]
  if shift in (0.06, 0.1):     # the same flow behind a fixed lead-in moves
    assert by_seconds[0] != by_seconds[1]


@pytest.mark.parametrize("period", [0.035 * f for f in (0.4, 0.67, 0.8, 1.0,
                                                       1.09, 1.43, 1.9)])
def test_finish_window_holds_the_same_work_at_any_step_time(period):
  """A faster system and a slower one are read over the same requests: the
  reading differs by what the time differs and by nothing else, though an
  uneven flow puts other steps at any fixed second. No clock closes it."""
  uneven = dict(turn=7, burst=2)
  base = r.FinishWindow(_Flow(**uneven), 64, 120)
  other = r.FinishWindow(_Flow(period=period, **uneven), 64, 120)
  assert other["tokens"] == base["tokens"] and other["finished"] == 120
  assert other["tok_s"] == pytest.approx(base["tok_s"] * 0.035 / period)


def test_finish_window_counts_what_the_edge_steps_finished():
  """Two requests end in the opening step and two in the closing one: those
  of the opening step lie before the window, those of the closing in it."""
  steps = [(1.0, 10, 0), (2.0, 30, 2), (3.0, 50, 2), (4.0, 70, 3),
           (5.0, 90, 5), (6.0, 99, 6)]
  win = r.FinishWindow(steps, 1, 3)
  assert (win["t_open"], win["t_close"]) == (2.0, 5.0)
  assert (win["finished_at_open"], win["finished"], win["tokens"]) == (
      2, 3, 60)


@pytest.mark.parametrize("steps,opening", [
    ([(1.0, 10, 0), (2.0, 20, 1)], 2),          # the opening one never ends
    ([(1.0, 10, 0), (2.0, 20, 1), (3.0, 30, 1)], 1),   # none after it
    ([(1.0, 10, 1), (40.0, 20, 5)], 1),         # four after it, not five
    ([], 1),
])
def test_finish_window_needs_both_its_finishes(steps, opening):
  with pytest.raises(ValueError, match="the window is requests"):
    r.FinishWindow(steps, opening, 5)


def test_the_plain_total_keeps_a_stall_that_the_median_of_loops_ignores():
  """train_tok_s is the plain total: all tokens over all the time."""
  intervals = [1.4] * 9 + [2.8]          # one loop lost a whole loop's time
  assert r.MedianOfLoops(intervals, 32768, 1) == pytest.approx(32768 / 1.4)
  assert r.PlainTotal(intervals, 32768, 1) == pytest.approx(
      32768 * 10 / (1.4 * 9 + 2.8))
  assert r.PlainTotal(intervals, 32768, 1) < r.MedianOfLoops(
      intervals, 32768, 1)
  assert r.MedianOfLoops(intervals, 32768, 4) == pytest.approx(
      32768 / 1.4 / 4)


def test_the_window_is_a_whole_number_of_loops():
  assert r.LoopsForWindow(30, 1.4) == math.ceil(30 / 1.4) == 22
  assert r.LoopsForWindow(10, 1.4) == 10      # never under ten readings
  assert r.LoopsForWindow(30, 1.5) == 20
  comps = [10.0, 11.4, 12.8, 14.3]
  assert r.Intervals(comps) == pytest.approx([1.4, 1.4, 1.5])


@pytest.mark.parametrize("intervals,want", [
    ([1.4, 1.41], False),                 # under three loops
    ([9.0, 1.6, 1.4, 1.405], True),       # the last two agree to 1%
    ([1.4, 1.4, 1.45], False),
    ([1.4, 1.4, 1.4, 1.4], True),
])
def test_warm_up_ends_when_two_loops_agree(intervals, want):
  assert r.WarmedUp(intervals, 3, 0.01) is want


def test_percentile_and_jitter():
  xs = list(range(1, 101))
  assert r.Percentile(xs, 50) == pytest.approx(50.5)
  assert r.Percentile(xs, 0) == 1 and r.Percentile(xs, 100) == 100
  assert r.Percentile([7.0], 95) == 7.0
  with pytest.raises(ValueError):
    r.Percentile([], 50)
  assert r.LoopJitter([1.0] * 20) == 0.0
  assert r.LoopJitter([1.0] * 18 + [2.0] * 2) > 0.0


def test_the_plain_total_is_all_tokens_over_the_whole_window():
  comps = [100.0, 101.4, 102.8, 104.2, 107.0]     # the last loop stalled
  intervals = r.Intervals(comps)
  assert r.PlainTotal(intervals, 32768, 1) == pytest.approx(
      4 * 32768 / (107.0 - 100.0))
  assert r.PlainTotal(intervals, 32768, 4) == pytest.approx(
      4 * 32768 / 7.0 / 4)


@pytest.mark.parametrize("values,median,full,left", [
    # one far-off run (a stalled window) is left out: the rest decide
    ([4000, 4010, 4020, 3178, 4005, 4015], 4007.5, 842, 20),
    # two far-off runs: one is left out, the other still decides
    ([4000, 4010, 4020, 3178, 3783, 4015], 4005.0, 842, 237),
    # the farthest run lies above the median
    ([60.2, 60.5, 61.2, 62.2, 64.6, 70.9], 61.7, 10.7, 4.4),
    # smallest and largest equally far: the one whose leaving narrows more
    ([10, 13, 15, 16, 20], 15, 10, 6),     # leaving 20 out: 16 - 10
    ([10, 14, 15, 17, 20], 15, 10, 6),     # leaving 10 out: 20 - 14
    # all alike, and sets too small to leave anything out
    ([5, 5, 5, 5], 5, 0, 0),
    ([3, 9], 6, 6, 6),
    ([7], 7, 0, 0),
])
def test_range_leaving_the_farthest_run_out(values, median, full, left):
  got = r.RangeLeavingOneOut(values)
  assert got == pytest.approx((median, full, left))
  assert r.RangeLeavingOneOut(list(reversed(values))) == pytest.approx(got)


def test_range_leaving_one_out_needs_a_reading():
  with pytest.raises(ValueError):
    r.RangeLeavingOneOut([])
