"""The client loop of a serving cell (serve_cell._Drive) against an engine
made up here: when a closed loop's window opens, how long the run then goes
on, and when the clients go round their list again."""

import json
import os
import time
import types

import pytest

from benchmarks.harness import serve_cell
from benchmarks.harness import traffic as traffic_lib

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks", "traffic")
_MAX_BATCH = 4


class _Handle:
  """Streams a token every `gap` seconds from the moment it was sent."""

  def __init__(self, new_tokens, gap):
    self._t0, self._n, self._gap = time.perf_counter(), new_tokens, gap
    self.finish_reason = "length"

  @property
  def _tokens(self):
    n = int((time.perf_counter() - self._t0) / self._gap)
    return [7] * min(self._n, n)

  @property
  def done(self):
    return len(self._tokens) == self._n


class _Engine:
  def __init__(self, gap):
    self.gap, self.sent = gap, []

  def Submit(self, prompt, new_tokens):
    self.sent.append(len(prompt))
    return _Handle(new_tokens, self.gap)


def _Ctx(seconds):
  notes = {}
  return types.SimpleNamespace(
      seconds=seconds, trace=False, trace_started=False, notes=notes,
      Note=lambda k, v, quiet=False: notes.__setitem__(k, v))


def _Docs(**over):
  with open(os.path.join(_DIR, "docs.json")) as f:
    tr = json.load(f)
  tr.update(prompt_len={"dist": "fixed", "value": 8},
            new_tokens={"dist": "fixed", "value": 4}, **over)
  return tr


def _Drive(tr, seconds, gap):
  ctx = _Ctx(seconds)
  geo = {"max_batch": _MAX_BATCH}
  requests = traffic_lib.Generate(tr, seconds, 3, _MAX_BATCH)
  prompts = {r.index: [1] * r.prompt_len for r in requests}
  engine = _Engine(gap)
  streams, gaps = serve_cell._Drive(ctx, engine, tr, requests, prompts, [],
                                    geo)
  return ctx, engine, requests, streams


def test_a_closed_loop_s_window_opens_when_every_client_was_served_once():
  tr = _Docs()
  assert tr["loop"] == "closed" and "lead_in_s" not in tr
  ctx, engine, requests, streams = _Drive(tr, seconds=0.3, gap=0.01)
  clients = traffic_lib.NumClients(tr, _MAX_BATCH)
  assert clients == 8
  done = sorted(s.done_at for s in streams if s.done_at is not None)
  # the client saw the window open with the clients-th finish, not sooner,
  # and kept the run going until the window's work was done: the requests
  # `seconds` hold at the rate the traffic file states
  in_window = traffic_lib.WindowRequests(tr, 0.3)
  assert in_window == round(tr["requests_per_s_hint"] * 0.3) >= 1
  assert done[clients - 1] <= ctx.t_win0 <= done[clients - 1] + 0.01
  assert len(done) >= clients + in_window
  assert done[clients + in_window - 1] >= done[-1] - 0.01   # and no longer
  assert ctx.t_win0 - ctx.t_gen0 >= 4 * 0.01        # a request's whole life
  # a closed loop: never more in flight than it has clients
  assert len(engine.sent) >= 2 * clients
  assert len(engine.sent) - len(done) <= clients


@pytest.mark.parametrize("gap", [0.02, 0.002, 0.0002])
def test_closed_loop_cycles_stay_zero_however_fast_the_system(gap):
  """The window's work is fixed, so a system a hundred times as fast draws
  no more requests than the list holds."""
  seconds = 0.4
  tr = _Docs(requests_per_s_hint=8 / (4 * 0.02))
  ctx, engine, requests, streams = _Drive(tr, seconds, gap)
  assert len(requests) == traffic_lib.ClosedLoopList(tr, seconds, _MAX_BATCH)
  assert ctx.notes["closed_loop_cycles"] == 0
  assert len(engine.sent) <= len(requests) == len(streams)


def test_a_window_s_work_that_runs_late_fails_the_run(monkeypatch):
  """A system far slower than the traffic file reckons with: the client
  waits for the window's work `seconds` x _WORK_PATIENCE and then gives no
  reading, rather than one of other work."""
  monkeypatch.setattr(serve_cell, "_WORK_PATIENCE", 1.5)
  tr = _Docs(requests_per_s_hint=1000.0)
  t0 = time.perf_counter()
  with pytest.raises(RuntimeError, match="of the window's 300 requests"):
    _Drive(tr, seconds=0.3, gap=0.01)
  assert time.perf_counter() - t0 < 0.3 * 1.5 + 8 * 4 * 0.01 + 0.5


def test_a_window_that_never_opens_ends_the_run(monkeypatch):
  monkeypatch.setattr(serve_cell, "_OPEN_PATIENCE_S", 0.1)
  with pytest.raises(RuntimeError, match="requests that open the window"):
    _Drive(_Docs(), seconds=0.2, gap=10.0)


def test_an_open_loop_keeps_its_window_in_seconds():
  with open(os.path.join(_DIR, "chat.json")) as f:
    tr = json.load(f)
  tr.update(lead_in_s=0.1, prompt_len={"dist": "fixed", "value": 8},
            new_tokens={"dist": "fixed", "value": 4})
  ctx, engine, requests, streams = _Drive(tr, seconds=0.2, gap=0.002)
  assert ctx.t_win0 == pytest.approx(ctx.t_gen0 + 0.1)
  assert ctx.t_win1 == pytest.approx(ctx.t_win0 + 0.2)
  assert len(engine.sent) == len(requests) == round(
      tr["rate_per_s"] * 0.1) + round(tr["rate_per_s"] * 0.2)
