"""The one general traffic generator. A traffic mix is a data file under
benchmarks/traffic/; nothing here knows a mix by name.

A seed permutes, it does not resample: the file states the length
distributions, the generator takes their quantile grid (the same multiset of
(prompt_len, new_tokens) pairs for every seed, sized to the window), and the
seed shuffles the order (inside small blocks of a low-discrepancy base order,
see Order; the mix may set `shuffle_block`), draws the token ids and places
the arrivals. Open-loop
arrivals are a Poisson process conditioned on its count: exactly
round(rate * seconds) arrivals at sorted uniform times. Every run of a cell
offers the same total work.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np

# pairs prompt quantiles with new-token quantiles; a constant of the
# benchmark, never the run's seed, so the multiset of pairs is fixed
_PAIRING_SEED = 20260927
_GOLDEN = 0.6180339887498949
_SHUFFLE_BLOCK = 4


@dataclasses.dataclass(frozen=True)
class Request:
  index: int
  due_s: float | None      # open loop: seconds after the generator's start
  prompt_len: int
  new_tokens: int
  sampled: bool            # False for the lead-in that fills the batch


def QuantileGrid(dist: dict, n: int) -> list[int]:
  """n integer lengths at the mid-quantiles (i + 0.5) / n of `dist`."""
  us = [(i + 0.5) / n for i in range(n)]
  kind = dist["dist"]
  if kind == "lognormal":
    nd = statistics.NormalDist()
    mu, sigma = np.log(dist["median"]), dist["sigma"]
    xs = [float(np.exp(mu + sigma * nd.inv_cdf(u))) for u in us]
  elif kind == "uniform":
    xs = [dist["min"] + u * (dist["max"] - dist["min"]) for u in us]
  elif kind == "fixed":
    xs = [dist["value"]] * n
  else:
    raise ValueError(f"unknown dist {kind!r}")
  lo, hi = dist.get("min", 1), dist.get("max", 1 << 30)
  return [int(min(max(round(x), lo), hi)) for x in xs]


def PairMultiset(traffic: dict, n: int) -> list[tuple[int, int]]:
  """The n (prompt_len, new_tokens) pairs every seed offers, in grid order."""
  prompts = QuantileGrid(traffic["prompt_len"], n)
  news = QuantileGrid(traffic["new_tokens"], n)
  perm = np.random.RandomState(_PAIRING_SEED).permutation(n)
  return [(prompts[i], news[int(perm[i])]) for i in range(n)]


def _Seed32(seed: int, salt: int) -> int:
  # --seed may exceed 32 signed bits; RandomState takes [0, 2**32)
  return (int(seed) * 2654435761 + salt) % (2**32)


def Order(n: int, rng, block: int) -> list[int]:
  """The order in which the n grid points are offered. Base order: by
  frac(i * golden ratio), a low-discrepancy sequence, so that every stretch
  of the run draws evenly from the whole distribution. The seed then
  shuffles inside consecutive blocks of `block`: still a permutation of the
  same multiset, but no seed can put all the long prompts first. block <= 1
  keeps the base order; block >= n is a free shuffle."""
  base = sorted(range(n), key=lambda i: ((i + 1) * _GOLDEN) % 1.0)
  if block <= 1:
    return base
  out = []
  for a in range(0, n, block):
    chunk = base[a:a + block]
    out.extend(chunk[j] for j in rng.permutation(len(chunk)))
  return out


def _Phase(traffic: dict, n: int, t0: float, t1: float, rng, sampled: bool,
           start_index: int) -> list[Request]:
  pairs = PairMultiset(traffic, n)
  order = Order(n, rng, int(traffic.get("shuffle_block", _SHUFFLE_BLOCK)))
  if traffic["loop"] == "open":
    dues = np.sort(rng.uniform(t0, t1, size=n))
  else:
    dues = [None] * n
  return [Request(start_index + j, None if dues[j] is None else float(dues[j]),
                  pairs[order[j]][0], pairs[order[j]][1], sampled)
          for j in range(n)]


def Generate(traffic: dict, seconds: float, seed: int, max_batch: int = 0
             ) -> list[Request]:
  """The run's requests, lead-in first. Every request, lead-in or not, has
  lengths from the mix's stated distributions: nothing is cut short.

  Open loop: exactly round(rate * lead_in_s) arrivals in the lead-in and
  exactly round(rate * seconds) in the window, each at sorted uniform times.
  Closed loop: `clients_per_slot * max_batch` clients all send at the start
  and each sends its next request when its last one finished; the list is
  what they draw from in order. Its window is a fixed amount of work between
  two finishes (WindowRequests), so the list is sized in work too
  (ClosedLoopList) and a closed loop has no `lead_in_s`. The first `clients`
  requests count as the lead-in (not sampled for latencies); the engine's
  queue serves them in list order, so the window too holds requests of this
  list.
  """
  rng = np.random.RandomState(_Seed32(seed, 1))
  if traffic["loop"] == "open":
    rate = float(traffic["rate_per_s"])
    lead = float(traffic.get("lead_in_s", 0.0))
    n_lead, n_win = round(rate * lead), round(rate * seconds)
  else:
    lead = 0.0
    n_lead = NumClients(traffic, max_batch)
    n_win = ClosedLoopList(traffic, seconds, max_batch) - n_lead
  out = _Phase(traffic, n_lead, 0.0, lead, rng, False, 0) if n_lead else []
  out += _Phase(traffic, n_win, lead, lead + seconds, rng, True, len(out))
  return out


def NumClients(traffic: dict, max_batch: int) -> int:
  return int(traffic["clients_per_slot"] * max_batch)


def WindowRequests(traffic: dict, seconds: float) -> int:
  """The work a closed loop's window holds: the requests `seconds` hold at
  `requests_per_s_hint`, the rate the cell turned over when the file was
  written."""
  return max(1, round(float(traffic["requests_per_s_hint"]) * seconds))


def ClosedLoopList(traffic: dict, seconds: float, max_batch: int) -> int:
  """How many requests a closed loop's list holds: the clients' first
  requests (the window opens when that many have finished), the window's own
  (WindowRequests), and one more in flight for every client when the last of
  those finishes. The window's work is fixed, so no system, however fast,
  goes round this list (`closed_loop_cycles` 0)."""
  return 2 * NumClients(traffic, max_batch) + WindowRequests(traffic, seconds)


def PromptIds(req: Request, seed: int, vocab_size: int) -> np.ndarray:
  rng = np.random.RandomState(_Seed32(seed, 1000003 + req.index))
  return rng.randint(1, vocab_size, size=req.prompt_len).astype(np.int32)


def TotalWork(requests: list[Request]) -> dict:
  """What a run offers: counted by tests to be equal across seeds."""
  return {"requests": len(requests),
          "prompt_tokens": sum(r.prompt_len for r in requests),
          "new_tokens": sum(r.new_tokens for r in requests)}
