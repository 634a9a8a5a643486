"""What a serving run notes beside its engine's long steps (the collector's
long passes, the compile events with their times), and
benchmarks/tools/sets.py: one row a run, and for every end-to-end metric the
set's range with the farthest run left out beside the cell's bound."""

import gc
import importlib.util
import json
import os
import time

import pytest

from benchmarks.harness import device
from benchmarks.harness import serve_cell
from benchmarks.harness import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _Tool(name):
  path = os.path.join(ROOT, "benchmarks", "tools", name + ".py")
  module_spec = importlib.util.spec_from_file_location("bench_" + name, path)
  mod = importlib.util.module_from_spec(module_spec)
  module_spec.loader.exec_module(mod)
  return mod


def _Sets():
  return _Tool("sets")


def test_gc_watch_counts_collections_by_generation():
  with serve_cell.GcWatch() as watch:
    gc.collect(0)
    gc.collect(2)
    gc.collect(2)
  assert watch.by_generation[0][0] == 1
  assert watch.by_generation[2][0] == 2
  assert watch.by_generation[2][1] > 0
  assert watch._On not in gc.callbacks
  before = dict(watch.by_generation)
  gc.collect(0)                      # closed: counts no more
  assert watch.by_generation == before


def test_gc_watch_keeps_a_long_collection_with_its_time():
  ticks = iter([10.0, 10.5, 11.0, 11.001, 12.0])
  watch = serve_cell.GcWatch(clock=lambda: next(ticks))
  watch._On("start", {})
  watch._On("stop", {"generation": 2})
  watch._On("start", {})
  watch._On("stop", {"generation": 0})
  assert watch.long == [(10.0, 2, 0.5)]
  assert watch.by_generation[2] == [1, 0.5]
  assert watch.by_generation[0][0] == 1
  watch._On("stop", {"generation": 1})     # a stop with no start: ignored
  assert 1 not in watch.by_generation


def test_compile_clock_keeps_each_event_with_its_time():
  clock = device.CompileClock.__new__(device.CompileClock)
  clock.events, clock.seconds, clock.hits, clock.misses = [], 0.0, 0, 0
  t0 = time.perf_counter()
  clock._OnDuration("/jax/core/compile/backend_compile_duration", 1.5)
  clock._OnDuration("/jax/some/other_duration", 9.0)
  clock._OnEvent("/jax/compilation_cache/cache_hits")
  assert clock.seconds == 1.5 and clock.hits == 1
  ((at, name, secs),) = clock.events
  assert name == "backend_compile_duration" and secs == 1.5
  assert t0 <= at <= time.perf_counter()


def _Line(value, setup, correct=True, failed=0):
  return {"correct": correct, "failed": failed, "attempted": 100,
          "metrics": {"serve_tok_s": {"value": value, "unit": "tokens/s"},
                      "setup_s": {"value": setup, "unit": "s"}}}


def _Notes(steps=447, period=66.2, excess=0.0):
  return {"step_stalls": {"steps": steps, "period_ms_median": period,
                          "steps_x_median_s": steps * period * 1e-3,
                          "stalls": 1 if excess else 0,
                          "stall_excess_s": excess, "slow_excess_s": 0.01,
                          "phases_ms": {"h2d": {"p50": 3.9, "p95": 5.0}}},
          "client_gaps": {"long_s": excess + 0.03, "long": 3,
                          "at_s_ms_thread_cpu_ms_process_cpu_ms": [
                              [-3.0, 110.0, 10.0, 20.0],     # in the lead-in
                              [4.0, 30.0, 29.0, 30.0],       # busy, not still
                              [9.5, 1e3 * excess, 0.0, 60.0]]},
          "gc": {"long_at_s_generation_ms": []},
          "setup": {"setup_s": 35.2, "setup_wall_s": 44.3,
                    "runtime_start_s": 9.1, "runtime_start_at_s": 4.0},
          "compile": {"seconds": 8.25},
          "serve_tok_s_between_finishes": {
              "t_open": 12.81, "t_close": 42.7, "seconds": 29.89,
              "tokens": 221000, "tok_s": 7393.8, "finished": 148},
          "closed_loop_cycles": 0, "compiles_in_window": []}


def test_a_row_says_where_the_run_s_window_went():
  sets = _Sets()
  row = sets.Row(7, {"rc": 0, "line": _Line(3178.0, 35.2),
                     "notes": _Notes(365, 66.7, 5.2)})
  assert row["serve_tok_s"] == 3178.0 and row["setup_s"] == 35.2
  assert (row["steps"], row["period_ms"], row["stalls"]) == (365, 66.7, 1)
  assert row["stall_excess_s"] == 5.2 and row["stood_still_s"] == 5.2
  assert row["stood_still_lead_in_s"] == 0.11
  assert row["steps_x_median_s"] == pytest.approx(24.35, abs=0.01)
  assert row["cycles"] == 0 and row["gc_long"] == 0
  # set-up: what the clock read, and the chip's bring-up left out of it
  assert (row["setup_wall_s"], row["runtime_start_s"]) == (44.3, 9.1)
  assert row["setup_s"] == pytest.approx(
      row["setup_wall_s"] - row["runtime_start_s"])
  assert row["compile_s"] == 8.25
  assert (row["opened_s"], row["window_s"], row["finished"]) == (
      12.81, 29.89, 148)
  assert row["correct"] is True and row["failed"] == 0
  # a run that gave no line
  dead = sets.Row(8, {"rc": 3, "line": None, "notes": {}})
  assert dead == {"seed": 8, "rc": 3, "correct": None, "failed": None}


@pytest.mark.parametrize("values,under", [
    ([4000, 4010, 4020, 3178, 4005, 4015], True),    # one stalled run
    ([4000, 4010, 4020, 3178, 3783, 4015], False),   # two
])
def test_the_summary_judges_a_set_as_the_driver_does(values, under):
  sets = _Sets()
  cell = spec.Cell(spec.LoadBenchmark(), "dense1b_serve_docs")
  rows = [sets.Row(i, {"rc": 0, "line": _Line(v, 30.0 + 0.1 * i),
                       "notes": _Notes()}) for i, v in enumerate(values)]
  by_metric = {s["metric"]: s for s in sets.Summary(cell, rows)}
  assert set(by_metric) == {"serve_tok_s", "setup_s"}
  tok = by_metric["serve_tok_s"]
  bound = next(m["bound"] for m in cell["end_to_end"]
               if m["name"] == "serve_tok_s")
  assert tok["bound"] == bound and tok["runs"] == 6
  assert tok["range"] == 842
  assert tok["under_bound"] is under
  assert tok["range_one_left_out_share"] == pytest.approx(
      tok["range_one_left_out"] / tok["median"])
  assert by_metric["setup_s"]["under_bound"] is True
  json.dumps(list(by_metric.values()))      # what it prints


def test_notes_are_read_off_a_run_s_output():
  sets = _Sets()
  lines = ['{"note": "closed_loop_cycles", "value": 0}', "W0927 noise",
           '{"note": "gc", "value": {"long_at_s_generation_ms": []}}',
           '{"note": broken', '{"correct": true}']
  assert sets._Notes(lines) == {"closed_loop_cycles": 0,
                                "gc": {"long_at_s_generation_ms": []}}


def _Record(seconds, period=0.035, turn=6):
  """[seconds since the start, tokens done, requests finished] a step: a
  request finishes every `turn` steps and the next one's prompt follows."""
  rec, tokens = [], 0
  for i in range(1, int(seconds / period) + 1):
    tokens += 14 + (500 if i % turn < 3 else 0)
    rec.append([round(period * i, 4), tokens, i // turn])
  return rec


def test_recorded_runs_are_read_again_behind_other_edges(tmp_path):
  offline = _Tool("window_offline")
  path = tmp_path / "docs.notes.jsonl"
  with open(path, "w") as f:
    for seed, seconds, trace in ((1, 60.0, 0), (2, 44.0, 0), (3, 60.0, 1)):
      f.write(json.dumps({"args": {"seed": seed, "trace": trace}, "notes": {
          "step_completions_from_start": _Record(seconds)}}) + "\n")
    f.write(json.dumps({"args": {"seed": 4, "trace": 0}, "notes": {}}) + "\n")
  records = offline.Records(str(path))
  assert [seed for seed, _ in records] == [1, 2]       # untraced, recorded
  long, short = offline.Table(records, opening=64, requests=140)
  want = (6 * 14 + 3 * 500) / (6 * 0.035)
  assert long["tok_s"] == pytest.approx(want, rel=1e-3)
  assert long["mean17.tok_s"] == pytest.approx(want, rel=1e-3)
  # a schedule that repeats: no other opening moves it
  for key in ("open-8", "open-4", "open+4", "open+8"):
    assert abs(long[key]) < 1e-3, key
  # 64 finishes take 13.4 s and 140 more 29.4 s: a record of 44 s holds the
  # window, and the one opened four requests later, but not the one opened
  # eight later, so not the seventeen's mean either
  assert short["tok_s"] == pytest.approx(want, rel=1e-3)
  assert short["open+4"] is not None and short["open+8"] is None
  assert short["mean17.tok_s"] is None
  assert offline.main([str(path), "--requests", "140"]) == 0


def test_a_sweep_s_row_says_whether_the_rate_was_sustained():
  sweep = _Tool("sweep")
  notes = {"offered": {"requests": 303},
           "client": {"finished_in_window": 251, "itl_gaps": 28000,
                      "ttft_samples": 252},
           "latency_summary": {
               "queue_wait_ms_median_by_third": [12.0, 13.1, 11.9],
               "open_at_end": 22, "itl_ms_p50_p95_p99": [26.0, 41.0, 55.0],
               "ttft_ms_p50_p95": [60.0, 140.0], "step_ms_median": 26.4,
               "steps_in_window": 1100, "gen_late_ms_p99": 1.9},
           "serve_tok_s_between_steps": {"tokens": 110000, "tok_s": 3666.0,
                                         "seconds": 30.0},
           "step_stalls": {"stall_excess_s": 0.1}}
  line = {"correct": True, "failed": 0, "attempted": 303,
          "metrics": {"itl_p95_ms": {"value": 41.0, "unit": "ms"}}}
  row = sweep.Row(8.4, 11, {"rc": 0, "line": line, "notes": notes})
  assert row["rate_per_s"] == 8.4 and row["itl_p95_ms"] == 41.0
  assert row["queue_wait_ms_median_by_third"] == [12.0, 13.1, 11.9]
  assert row["open_at_end"] == 22 and row["failed"] == 0
  assert row["tokens_per_step"] == 100.0
  dead = sweep.Row(9.0, 12, {"rc": 1, "line": None, "notes": {}})
  assert dead["rc"] == 1 and dead["correct"] is None


@pytest.mark.parametrize("backlog,want", [
    ([False, False, False, True, True], [2.1, 2.6, 3.1]),
    ([False, False, False, False, False], [3.1, 3.6]),     # no bend in reach
    ([False, True, False, True, True], [2.1, 2.6, 3.1]),   # a stop at 2.1
    ([True, True], [1.6, 2.1]),
])
def test_a_sweep_runs_the_rates_round_the_bend_again(backlog, want):
  sweep = _Tool("sweep")
  rates = [1.6, 2.1, 2.6, 3.1, 3.6][:len(backlog)]
  assert sweep.Bend(rates, backlog) == want
  row = {"rc": 0, "failed": 0, "queue_wait_ms_median_by_third": [9, 11, 10]}
  assert sweep.Backlog(row, 500.0) is False
  assert sweep.Backlog(dict(row, failed=1), 500.0) is True
  assert sweep.Backlog(dict(row, queue_wait_ms_median_by_third=[
      40, 700, 1900]), 500.0) is True
  assert sweep.Backlog({"rc": 1, "failed": None}, 500.0) is True
