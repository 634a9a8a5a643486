"""How a run turns its window into one number, and its outputs into
`correct`. Pure arithmetic, no JAX."""

from __future__ import annotations

import math
import statistics

import numpy as np


def Percentile(values, q: float) -> float:
  """q in [0, 100]; linear interpolation between order statistics."""
  xs = sorted(values)
  if not xs:
    raise ValueError("no samples")
  if len(xs) == 1:
    return float(xs[0])
  k = (len(xs) - 1) * q / 100.0
  lo = math.floor(k)
  hi = min(lo + 1, len(xs) - 1)
  return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def LoopsForWindow(seconds: float, warm_loop_s: float, at_least: int = 10
                   ) -> int:
  """The window is a whole number of loops: ceil(seconds / warm loop time),
  and never fewer than `at_least` readings."""
  return max(at_least, math.ceil(seconds / warm_loop_s))


def Intervals(completions: list[float]) -> list[float]:
  """completions[0] is the fenced end of the last warm-up loop; each later
  entry is one loop's completion. One reading per loop."""
  return [b - a for a, b in zip(completions, completions[1:])]


def WarmedUp(intervals: list[float], min_loops: int = 3, tol: float = 0.01
             ) -> bool:
  """At least `min_loops` loops done and the last two agree to `tol`."""
  if len(intervals) < max(min_loops, 2):
    return False
  a, b = intervals[-2], intervals[-1]
  return abs(a - b) <= tol * min(a, b)


def PlainTotal(intervals: list[float], tokens_per_loop: float, chips: int
               ) -> float:
  """All tokens of the window over all its time, per chip: the end-to-end
  rate. The window is a whole number of loops, from the fenced completion
  of the last warm-up loop to the completion of the last one, so nothing is
  quantised and a stall inside it counts in full."""
  return tokens_per_loop * len(intervals) / sum(intervals) / chips


def MedianOfLoops(intervals: list[float], tokens_per_loop: float, chips: int
                  ) -> float:
  """tokens per loop over the MEDIAN loop interval, per chip: the steady
  state's rate, which one stall does not move. A per-layer reading; where
  it and PlainTotal part, the window held a stall."""
  return tokens_per_loop / statistics.median(intervals) / chips


def LoopJitter(intervals: list[float]) -> float:
  """(p95 - p50) / p50 of the window's loop intervals, in percent."""
  p50 = Percentile(intervals, 50)
  return 100.0 * (Percentile(intervals, 95) - p50) / p50


def TokenWindowRate(step_records: list[tuple[float, int]], t_start: float,
                    t_end: float) -> tuple[float, int, float]:
  """Token-granular throughput between two step completions.

  step_records: (completion time, cumulative tokens done) after every engine
  step, in order; tokens = prompt tokens whose KV entered the cache (computed
  or shared) + tokens streamed. Takes the first and the last step that
  complete inside [t_start, t_end] and returns (tokens/s between those two
  completions, tokens, seconds). A request cut by either edge counts for
  exactly what it got done between them.
  """
  inside = [(t, n) for t, n in step_records if t_start <= t <= t_end]
  if len(inside) < 2:
    raise ValueError(f"{len(inside)} step completions inside the window")
  (t0, n0), (t1, n1) = inside[0], inside[-1]
  return (n1 - n0) / (t1 - t0), n1 - n0, t1 - t0


def FinishWindow(step_records: list[tuple[float, int, int]], opening: int,
                 requests: int) -> dict:
  """A window whose edges the work defines, for a closed loop whose flow
  comes in bursts (one per request admitted).

  step_records: (completion time, cumulative tokens done, cumulative requests
  finished) after every engine step since the clients' start, in order. The
  window opens at the completion of the step in which the `opening`-th
  request finishes and closes at the completion of the step in which the
  (`opening` + `requests`)-th does: a fixed amount of work, the same requests
  at any step time, so that a faster or slower system reads higher or lower
  by what its time differs and by nothing else. There is no other way to
  close it: records that end before that finish raise ValueError.
  Returns tok_s (tokens between the two completions over the time between
  them), tokens, seconds, t_open, t_close, and the requests finished by the
  opening step (finished_at_open) and between the two (finished; more than
  `requests` where the closing step finished several).
  """
  opened = closed = None
  finished = 0
  for t, n, f in step_records:
    finished = f
    if opened is None:
      if f >= opening:
        opened = (t, n, f)
    elif f >= opening + requests:
      closed = (t, n, f)
      break
  if closed is None:
    raise ValueError(f"the window is requests {opening + 1} to "
                     f"{opening + requests}: {finished} finished")
  (t_open, n_open, f_open), (t_close, n_close, f_close) = opened, closed
  return {"tok_s": (n_close - n_open) / (t_close - t_open),
          "tokens": n_close - n_open, "seconds": t_close - t_open,
          "t_open": t_open, "t_close": t_close,
          "finished_at_open": f_open, "finished": f_close - f_open}


def CompareLogits(got, want, tol: float) -> tuple[bool, dict]:
  """The program's logits [K, V] at K sampled positions against the plain
  reference's [K, V]: every logit of every position within `tol`, all
  finite. Numbers against numbers: an argmax would be decided by the token
  the untrained model echoes and could not fail."""
  got = np.asarray(got, np.float32)
  want = np.asarray(want, np.float32)
  if got.shape != want.shape:
    return False, {"error": f"logits {got.shape}, reference {want.shape}",
                   "tolerance": tol}
  diff = np.abs(got - want)
  ok = bool(np.all(np.isfinite(got)) and np.all(np.isfinite(want))
            and diff.max() <= tol)
  return ok, {"max_abs_diff": float(diff.max()),
              "mean_abs_diff": float(diff.mean()),
              "max_abs_diff_by_position": [round(float(x), 4)
                                           for x in diff.max(-1)],
              "argmax_agree": int((got.argmax(-1) == want.argmax(-1)).sum()),
              "positions": int(got.shape[0]), "tolerance": tol}


def RangeLeavingOneOut(values) -> tuple[float, float, float]:
  """(median, range, range with the farthest run left out) of one metric's
  readings over a set of runs: how the driver's check judges whether a set is
  steady enough to tell a change ("a spread leaves out the run farthest from
  its median where that narrows it"). The run left out is the one farthest
  from the median, which is the smallest or the largest; where both lie
  equally far, the one whose leaving narrows the range more. One far-off run
  in a set therefore does no harm, and two do. Fewer than three runs leave
  nothing out."""
  xs = sorted(float(v) for v in values)
  if not xs:
    raise ValueError("no readings")
  median = statistics.median(xs)
  full = xs[-1] - xs[0]
  if len(xs) < 3:
    return median, full, full
  without_low, without_high = xs[-1] - xs[1], xs[-2] - xs[0]
  low_far, high_far = median - xs[0], xs[-1] - median
  if low_far > high_far:
    left = without_low
  elif high_far > low_far:
    left = without_high
  else:
    left = min(without_low, without_high)
  return median, full, left
