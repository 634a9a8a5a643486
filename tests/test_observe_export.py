"""Fleet-facing telemetry (observe/export|goodput|watchdog|aggregate):
endpoint exposition + parse-back, goodput/MFU accounting, stall watchdog
trips, fleet merge, and the live-engine/executor endpoint integration."""

import json
import re
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from lingvo_tpu import observe
from lingvo_tpu.observe import aggregate
from lingvo_tpu.observe import export as export_lib
from lingvo_tpu.observe import goodput as goodput_lib
from lingvo_tpu.observe import schema as observe_schema
from lingvo_tpu.observe import watchdog as watchdog_lib


def _Get(url, timeout=10.0):
  """(status code, body str) — 4xx/5xx don't raise."""
  try:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
      return resp.status, resp.read().decode("utf-8")
  except urllib.error.HTTPError as e:
    return e.code, e.read().decode("utf-8")


def _ParsePrometheus(text):
  """Prometheus text -> ({name: value}, {name: {label_part: value}})."""
  plain, labeled = {}, {}
  for line in text.splitlines():
    if not line or line.startswith("#"):
      continue
    name_part, value = line.rsplit(" ", 1)
    if "{" in name_part:
      name, labels = name_part.split("{", 1)
      labeled.setdefault(name, {})[labels.rstrip("}")] = value
    else:
      plain[name_part] = float(value)
  return plain, labeled


class _FakeClock:
  def __init__(self, t=100.0):
    self.t = t

  def __call__(self):
    return self.t


class _FakeProfileWindow:
  """ProfileWindow stand-in with the same arm/tick/close surface — the
  real one drives the (seconds-per-start/stop, process-singleton) jax
  profiler, which test_observe.py covers."""

  def __init__(self, logdir, steps=0):
    self.logdir, self.steps_remaining, self.stopped = logdir, steps, False

  def Start(self):
    return self

  def Stop(self):
    self.stopped = True

  def StepDone(self):
    self.steps_remaining -= 1
    return self.steps_remaining <= 0


# -- Prometheus exposition ----------------------------------------------------


class TestPrometheusText:

  def test_metric_name_sanitization(self):
    assert export_lib.MetricName("serving/ttft_s") == "serving_ttft_s"
    assert export_lib.MetricName("a b-c.d") == "a_b_c_d"
    assert export_lib.MetricName("0weird") == "_0weird"

  def test_parse_back_counters_gauges_histograms_strings(self):
    reg = observe.MetricsRegistry("t")
    reg.Counter("serving/steps").Inc(7)
    reg.Gauge("serving/queue_depth").Set(3)
    reg.Gauge("serving/kv_dtype").Set("int8")
    reg.SectionFn("scheduler", lambda: {"active": 2, "paged": True})
    h = reg.Histogram("serving/ttft_s", bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
      h.Observe(v)

    text = export_lib.PrometheusText(reg.Snapshot(), reg.Describe())
    plain, labeled = _ParsePrometheus(text)

    assert plain["serving_steps"] == 7
    assert plain["serving_queue_depth"] == 3
    assert plain["scheduler_active"] == 2
    assert plain["scheduler_paged"] == 1          # bool -> 0/1 gauge
    assert labeled["serving_kv_dtype_info"] == {'value="int8"': "1"}
    # histogram: cumulative buckets, +Inf == count
    b = labeled["serving_ttft_s_bucket"]
    assert b['le="0.1"'] == "1"
    assert b['le="1.0"'] == "3"
    assert b['le="10.0"'] == "4"
    assert b['le="+Inf"'] == "5"
    assert plain["serving_ttft_s_count"] == 5
    assert plain["serving_ttft_s_sum"] == pytest.approx(56.05)
    # TYPE lines carry the Describe() kind
    assert "# TYPE serving_steps counter" in text
    assert "# TYPE serving_queue_depth gauge" in text

  def test_snapshot_only_keys_fall_back_to_gauge(self):
    # a section key absent from Describe() (e.g. a merged snapshot)
    assert export_lib.KindOf("nope/x", {}) == "gauge"
    assert export_lib.KindOf("s/x", {"s": "section"}) == "gauge"
    assert export_lib.KindOf("c", {"c": "counter"}) == "counter"

  def test_build_info_matches_schema(self):
    info = export_lib.BuildInfo()
    assert set(info) == set(observe_schema.BUILD_INFO_KEYS)
    assert info["jax_version"] == jax.__version__


class TestHistogramQuantiles:

  def test_interpolated_quantiles(self):
    reg = observe.MetricsRegistry("t")
    h = reg.Histogram("lat", bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
      h.Observe(v)
    q = observe.HistogramQuantiles(reg.Snapshot()["lat"], qs=(0.5, 0.99))
    # rank 2.5 lands in bucket (0.1, 1.0] holding obs #2..3:
    # 0.1 + 0.9 * (2.5 - 1) / 2 = 0.775
    assert q[0.5] == pytest.approx(0.775)
    assert q[0.99] == pytest.approx(10.0)   # overflow clamps to top bound

  def test_empty_histogram(self):
    reg = observe.MetricsRegistry("t")
    reg.Histogram("lat", bounds=(1.0,))
    q = observe.HistogramQuantiles(reg.Snapshot()["lat"])
    assert q == {0.5: 0.0, 0.99: 0.0}

  def test_summary_writer_emits_quantiles(self, tmp_path):
    from lingvo_tpu.core import summary_utils
    reg = observe.MetricsRegistry("t")
    h = reg.Histogram("lat", bounds=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
      h.Observe(v)
    w = summary_utils.SummaryWriter(str(tmp_path), enabled=False)
    written = {}
    w.Scalars = lambda values, step, prefix="": written.update(values)
    w.FromRegistry(reg, step=1)
    assert written["lat/count"] == 5
    assert written["lat/p50"] == pytest.approx(0.775)
    assert written["lat/p99"] == pytest.approx(10.0)


# -- StatusServer endpoints ---------------------------------------------------


class TestStatusServer:

  def test_endpoints_roundtrip(self):
    reg = observe.MetricsRegistry("t")
    reg.Counter("serving/steps").Inc(3)
    srv = export_lib.StatusServer(
        0, registry=reg, name="unit",
        statusz_fn=lambda: {"compile": {"step": {"calls": 1}}}).Start()
    try:
      code, body = _Get(srv.Url("/metrics"))
      assert code == 200
      plain, _ = _ParsePrometheus(body)
      assert plain["serving_steps"] == 3

      code, body = _Get(srv.Url("/statusz"))
      assert code == 200
      doc = observe_schema.ValidateStatusz(json.loads(body))
      assert doc["name"] == "unit"
      assert doc["snapshot"]["serving/steps"] == 3
      assert doc["stats"]["compile"]["step"]["calls"] == 1

      assert _Get(srv.Url("/traces"))[0] == 404     # no TraceRecorder
      code, body = _Get(srv.Url("/healthz"))
      assert code == 200 and json.loads(body) == {
          "healthy": True, "watchdog": False}
      assert _Get(srv.Url("/nope"))[0] == 404
    finally:
      srv.Stop()

  def test_statusz_fn_error_returns_500_not_crash(self):
    srv = export_lib.StatusServer(
        0, registry=observe.MetricsRegistry("t"),
        statusz_fn=lambda: 1 / 0).Start()
    try:
      code, body = _Get(srv.Url("/statusz"))
      assert code == 500 and "ZeroDivisionError" in body
      assert _Get(srv.Url("/metrics"))[0] == 200    # server survives
    finally:
      srv.Stop()

  def test_healthz_flips_on_stall_and_arms_capture(self, tmp_path,
                                                   monkeypatch):
    # stub the flight recorder: the real jax profiler costs seconds per
    # start/stop and is covered by test_observe.py; this test owns the
    # watchdog arm/tick/close lifecycle only
    monkeypatch.setattr(watchdog_lib.profile_lib, "ProfileWindow",
                        _FakeProfileWindow)
    clock = _FakeClock()
    reg = observe.MetricsRegistry("t")
    wd = watchdog_lib.StallWatchdog(
        reg, min_interval_s=0.1, stall_factor=10.0,
        capture_logdir=str(tmp_path), clock=clock)
    srv = export_lib.StatusServer(0, registry=reg, watchdog=wd).Start()
    try:
      for _ in range(3):
        clock.t += 0.2
        wd.Beat()
      assert _Get(srv.Url("/healthz"))[0] == 200
      clock.t += 100.0   # the loop hangs; only the scrape thread runs
      code, body = _Get(srv.Url("/healthz"))
      assert code == 503
      stats = json.loads(body)
      assert stats["healthy"] is False
      assert "no_heartbeat" in stats["tripped"]
      assert stats["capture_armed"] is True       # flight recorder armed
      assert reg.Snapshot()["watchdog/trips_total"] == 1
      assert reg.Snapshot()["watchdog/trips_no_heartbeat"] == 1
      # two normal-pace beats clear it (the first beat's 100s step is
      # itself a genuine step_regression)
      clock.t += 0.2
      wd.Beat()
      clock.t += 0.2
      wd.Beat()
      assert _Get(srv.Url("/healthz"))[0] == 200
      assert reg.Snapshot()["watchdog/trips_total"] == 2  # once per episode
    finally:
      srv.Stop()
      if wd.capture is not None:   # close the still-armed flight recorder:
        wd.capture.Stop()          # the jax profiler is a process singleton


# -- goodput + MFU ------------------------------------------------------------


class TestGoodput:

  def test_buckets_sum_to_wall(self):
    clock = _FakeClock(0.0)
    reg = observe.MetricsRegistry("t")
    gp = goodput_lib.GoodputTracker(registry=reg, clock=clock)
    with gp.Track("compile"):
      clock.t += 3.0
    with gp.Track("step"):
      clock.t += 6.0
    gp.Add("infeed_wait", 1.0)   # attributed without advancing the clock
    clock.t += 3.0               # unaccounted wall -> lands in `other`
    stats = gp.Stats()
    assert set(stats) == set(observe_schema.GOODPUT_STATS_KEYS)
    assert stats["compile_s"] == pytest.approx(3.0)
    assert stats["step_s"] == pytest.approx(6.0)
    assert stats["infeed_wait_s"] == pytest.approx(1.0)
    assert stats["other_s"] == pytest.approx(2.0)   # 10 accounted, 12 wall
    assert stats["wall_s"] == pytest.approx(12.0)
    bucket_sum = sum(stats[f"{b}_s"] for b in observe_schema.GOODPUT_BUCKETS)
    assert bucket_sum == pytest.approx(stats["wall_s"])
    assert stats["productive_ratio"] == pytest.approx(0.5)
    # registered as a lazy section
    assert reg.Snapshot()["goodput/step_s"] == pytest.approx(6.0)

  def test_unknown_bucket_asserts(self):
    gp = goodput_lib.GoodputTracker(clock=_FakeClock())
    with pytest.raises(AssertionError):
      gp.Add("lunch", 1.0)

  def test_publish_mfu(self):
    reg = observe.MetricsRegistry("t")
    reg.Gauge("train/train_steps_per_second").Set(2.0)
    goodput_lib.PublishMfu(reg, flops_per_step=25.0, peak_flops=100.0)
    snap = reg.Snapshot()
    assert snap["train/flops_per_step"] == 25.0
    assert snap["train/mfu"] == pytest.approx(0.5)   # 25*2/100
    reg.Gauge("train/train_steps_per_second").Set(None)  # not yet tracked
    assert reg.Snapshot()["train/mfu"] == 0.0

  def test_track_excluding_compile(self):
    clock = _FakeClock(0.0)
    gp = goodput_lib.GoodputTracker(clock=clock)
    with gp.TrackExcludingCompile("step"):
      clock.t += 5.0
      gp.Add("compile", 2.0)   # a lazy jit compile observed mid-window
    stats = gp.Stats()
    assert stats["step_s"] == pytest.approx(3.0)   # 5 wall - 2 compile
    assert stats["compile_s"] == pytest.approx(2.0)
    # more compile than wall (clock skew) clamps at zero, never negative
    with gp.TrackExcludingCompile("eval"):
      clock.t += 1.0
      gp.Add("compile", 4.0)
    assert gp.Stats()["eval_s"] == 0.0

  def test_the_startup_records_listener_feeds_the_tracker(self):
    """The compile bucket reads observe.profile's start-up record (the one
    listener): self seconds, so an event nested in another counts once, by
    the thread that compiled, from the tracker's last Reset on."""
    from lingvo_tpu.observe import profile as profile_lib
    clock = _FakeClock(100.0)
    rec = profile_lib.StartupRecord(clock=clock)
    trace = "/jax/core/compile/jaxpr_trace_duration"
    backend = "/jax/core/compile/backend_compile_duration"
    rec.EventBegins(backend)
    clock.t += 1.0
    rec.EventEnds(backend, "before")            # before the tracker: not its
    gp = goodput_lib.GoodputTracker(clock=clock, compile_record=rec)
    rec.EventBegins(trace)
    clock.t += 1.0
    rec.EventBegins(trace)                      # an inner jit, nested
    clock.t += 0.5
    rec.EventEnds(trace, "inner")
    clock.t += 1.0
    rec.EventEnds(trace, "outer")               # 2.5 s in all, not 3.0
    rec.EventBegins(backend, thread=7)
    clock.t += 2.0
    rec.EventEnds(backend, "elsewhere", thread=7)
    assert gp.Stats()["compile_s"] == pytest.approx(4.5)
    assert gp.CompileSeconds() == pytest.approx(2.5)
    assert gp.CompileSeconds(7) == pytest.approx(2.0)
    gp.Add("compile", 0.25)                     # a caller's own still adds
    assert gp.Stats()["compile_s"] == pytest.approx(4.75)
    gp.Reset()
    assert gp.Stats()["compile_s"] == 0.0 and gp.CompileSeconds(7) == 0.0
    # the process's tracker reads the process's record
    assert goodput_lib.Get()._record is profile_lib.Startup()
    assert not hasattr(goodput_lib, "_OnJaxEvent")

  def test_peak_flops_lookup(self):
    assert goodput_lib.PeakFlopsPerDevice("TPU v4") == 275e12
    assert goodput_lib.PeakFlopsPerDevice("TPU v5p slice") == 459e12
    assert (goodput_lib.PeakFlopsPerDevice("weird accelerator")
            == goodput_lib.DEFAULT_PEAK_FLOPS)


class TestWatchdog:

  def test_close_drops_armed_capture(self, tmp_path, monkeypatch):
    monkeypatch.setattr(watchdog_lib.profile_lib, "ProfileWindow",
                        _FakeProfileWindow)
    clock = _FakeClock()
    wd = watchdog_lib.StallWatchdog(
        min_interval_s=0.1, capture_logdir=str(tmp_path), clock=clock)
    for _ in range(3):
      clock.t += 0.2
      wd.Beat()
    clock.t += 100.0
    assert wd.Check()["healthy"] is False
    armed = wd.capture
    assert armed is not None               # flight recorder armed
    wd.Close()                             # teardown mid-window
    assert wd.capture is None and armed.stopped   # singleton released

  def test_step_regression_and_recovery(self):
    clock = _FakeClock()
    wd = watchdog_lib.StallWatchdog(clock=clock, regression_factor=4.0)
    for _ in range(5):
      wd.Beat(step_time_s=0.2)
    assert wd.Check()["healthy"] is True
    wd.Beat(step_time_s=2.0)   # 10x the EMA
    stats = wd.Check()
    assert stats["healthy"] is False and "step_regression" in stats["tripped"]
    wd.Beat(step_time_s=0.2)
    assert wd.Check()["healthy"] is True

  def test_queue_stall_trip_and_drain(self):
    clock = _FakeClock()
    wd = watchdog_lib.StallWatchdog(clock=clock, queue_window=3)
    for depth, retired in ((1, 0), (3, 0), (6, 0)):
      wd.ObserveQueue(depth, retired)
    stats = wd.Check()
    assert stats["healthy"] is False and "queue_stall" in stats["tripped"]
    wd.ObserveQueue(2, 5)   # retirement resumed
    assert wd.Check()["healthy"] is True

  def test_idle_refresh_is_not_a_stall(self):
    # a loop with no work keeps liveness fresh via Idle() without
    # polluting the step-time EMA
    clock = _FakeClock()
    wd = watchdog_lib.StallWatchdog(clock=clock, stall_factor=10.0,
                                    min_interval_s=1.0)
    wd.Beat(step_time_s=0.01)
    ema = wd.Stats()["step_ema_s"]
    for _ in range(40):   # 200s of idle, way past the 10s trip window
      clock.t += 5.0
      wd.Idle()
    stats = wd.Check()
    assert stats["healthy"] is True and stats["trips"] == 0
    assert stats["step_ema_s"] == ema   # idle never fed the EMA
    # but a hung loop (no Idle ticks either) still trips
    clock.t += 50.0
    stats = wd.Check()
    assert stats["healthy"] is False and "no_heartbeat" in stats["tripped"]

  def test_stats_keys_match_schema(self):
    wd = watchdog_lib.StallWatchdog(clock=_FakeClock())
    assert set(wd.Stats()) == set(observe_schema.WATCHDOG_STATS_KEYS)


# -- fleet aggregation --------------------------------------------------------


def _Replica(label, tokens, depth):
  reg = observe.MetricsRegistry(label)
  reg.Counter("serving/tokens_emitted").Inc(tokens)
  reg.SectionFn("scheduler", lambda: {"queue_depth": depth})
  h = reg.Histogram("serving/ttft_s", bounds=(0.1, 1.0))
  for _ in range(tokens):
    h.Observe(0.5)
  return label, reg.Snapshot(), reg.Describe()


class TestAggregate:

  def test_merge_snapshots(self):
    merged = aggregate.MergeSnapshots([_Replica("a", 5, 1),
                                       _Replica("b", 7, 4)])
    assert merged["replicas"] == ["a", "b"]
    assert merged["fleet"]["serving/tokens_emitted"] == 12   # counters sum
    hist = merged["fleet"]["serving/ttft_s"]
    assert hist["count"] == 12 and hist["counts"][1] == 12   # buckets merge
    # gauges/sections stay per-replica
    assert merged["per_replica"]["a"]["scheduler/queue_depth"] == 1
    assert merged["per_replica"]["b"]["scheduler/queue_depth"] == 4

  def test_incompatible_hist_bounds_keep_larger(self):
    a = {"count": 9, "sum": 1.0, "mean": 0.1, "bounds": [1.0],
         "counts": [9, 0]}
    b = {"count": 2, "sum": 1.0, "mean": 0.5, "bounds": [2.0],
         "counts": [2, 0]}
    assert aggregate._MergeHist(a, b)["count"] == 9

  def test_least_loaded_and_statusz_merge(self):
    docs = {}
    for label, tokens, depth in (("a", 5, 1), ("b", 7, 4)):
      _, snap, desc = _Replica(label, tokens, depth)
      docs[label] = {"name": label, "build": export_lib.BuildInfo(),
                     "snapshot": snap, "describe": desc, "stats": None}
    docs["dead"] = {"error": "URLError: refused"}
    assert aggregate.LeastLoaded(docs) == "a"
    merged = aggregate.MergeStatusz(docs)     # error replica skipped
    assert merged["replicas"] == ["a", "b"]
    assert aggregate.LeastLoaded({"dead": {"error": "x"}}) is None

  def test_fleet_report_tool(self):
    from tools import fleet_report
    docs = {}
    for label, tokens, depth in (("a", 5, 1), ("b", 7, 4)):
      _, snap, desc = _Replica(label, tokens, depth)
      docs[label] = {"name": label, "build": export_lib.BuildInfo(),
                     "snapshot": snap, "describe": desc, "stats": None}
    docs["c"] = {"error": "URLError: connection refused"}
    report = fleet_report.FleetReport(docs)
    assert "2 live, 1 unreachable" in report
    assert "serving/tokens_emitted" in report and "12" in report
    assert "least-loaded replica" in report and "a" in report
    assert "DOWN c" in report
    assert "jain fairness" in report

  def test_fleet_report_fairness_and_utilization(self):
    from tools import fleet_report
    assert fleet_report.JainFairness([]) == 1.0
    assert fleet_report.JainFairness([0, 0]) == 1.0      # idle fleet: fair
    assert fleet_report.JainFairness([5, 5, 5]) == 1.0
    assert abs(fleet_report.JainFairness([9, 0, 0]) - 1 / 3) < 1e-9
    docs = {
        "a": {"snapshot": {"serving/tokens_emitted": 30,
                           "serving/prompt_tokens": 90,
                           "scheduler/queue_depth": 2}},
        "b": {"snapshot": {"serving/tokens_emitted": 10,
                           "serving/prompt_tokens": 10}},
        "dead": {"error": "URLError: refused"},          # never a row
    }
    util = fleet_report.Utilization(docs)
    assert set(util["per_replica"]) == {"a", "b"}
    assert util["per_replica"]["a"]["decode_share"] == 0.75
    assert util["per_replica"]["b"]["prefill_share"] == 0.1
    assert util["per_replica"]["b"]["queue_depth"] == 0  # missing -> 0
    assert abs(util["decode_fairness"]
               - fleet_report.JainFairness([30, 10])) < 1e-9
    assert util["prefill_fairness"] < util["decode_fairness"]  # 90/10 skew

  def test_scrape_validates_against_live_server(self):
    reg = observe.MetricsRegistry("t")
    reg.Counter("serving/steps").Inc(1)
    srv = export_lib.StatusServer(0, registry=reg, name="scrapee").Start()
    try:
      doc = aggregate.Scrape(f"{srv.host}:{srv.port}")   # bare host:port
      assert doc["name"] == "scrapee"
      docs = aggregate.ScrapeAll([srv.Url("/statusz"),
                                  "127.0.0.1:1/statusz"])
      assert sum("error" in d for d in docs.values()) == 1
    finally:
      srv.Stop()


class TestTraceReportMerged:

  def _Trace(self, base_ms):
    reqs = {str(i): {"slot": i, "prompt_tokens": 3, "tokens": 4, "pages": 2,
                     "queue_wait_s": 0.001, "ttft_s": base_ms * 1e-3,
                     "tpot_s": base_ms * 1e-3 / 4,
                     "total_s": base_ms * 2e-3, "finish_reason": "length"}
            for i in range(1, 4)}
    return {"traceEvents": [], "perRequest": reqs}

  def test_merged_per_replica_table(self, tmp_path):
    from tools import trace_report
    paths = []
    for label, base in (("a", 10.0), ("b", 30.0)):
      path = str(tmp_path / f"{label}.json")
      with open(path, "w") as f:
        json.dump(self._Trace(base), f)
      paths.append(path)
    report = trace_report.MergedReport(
        {p: trace_report.LoadTrace(p) for p in paths})
    lines = report.splitlines()
    assert any("FLEET" in l for l in lines)
    rows = [l for l in lines if l.endswith(tuple("0123456789"))
            and not l.startswith("-")]
    assert len(rows) >= 3                       # 2 replicas + fleet
    assert trace_report.main(paths) == 0        # multi-file CLI path
    assert trace_report.main([]) == 2


# -- live integration: serving engine + executor endpoints --------------------


def _TinyLmParams():
  from lingvo_tpu.models.lm import layers as lm_layers
  return lm_layers.TransformerLm.Params().Set(
      name="lm", vocab_size=64, model_dim=32, num_layers=2, num_heads=2,
      hidden_dim=64, use_rotary=True)


@pytest.fixture(scope="module")
def tiny_lm():
  task = _TinyLmParams().Instantiate()
  task.FinalizePaths()
  theta = task.InstantiateVariables(jax.random.PRNGKey(0))
  return task, theta


class TestLiveEngineEndpoints:

  def test_engine_serves_all_endpoints(self, tiny_lm):
    from lingvo_tpu.serving import engine as engine_lib
    task, theta = tiny_lm
    eng = engine_lib.ServingLoop(
        task, theta, page_size=4, num_pages=16, max_batch=2,
        max_seq_len=32, prefill_chunk=4, default_max_new=4,
        serve_port=0, watchdog=True)
    eng.Start()
    try:
      tokens = eng.Submit([1, 2, 3], 3).Result(timeout=600)
      assert tokens
      url = eng.status_server.Url

      code, body = _Get(url("/metrics"))
      assert code == 200
      plain, labeled = _ParsePrometheus(body)
      # every schema engine counter is a Prometheus series
      for key in observe_schema.ENGINE_COUNTER_KEYS:
        assert f"serving_{key}" in plain, key
      assert plain["serving_tokens_emitted"] >= len(tokens)

      code, body = _Get(url("/statusz"))
      assert code == 200
      doc = observe_schema.ValidateStatusz(json.loads(body))
      assert doc["name"] == "serving"
      stats = doc["stats"]                      # engine Stats(), validated
      observe_schema.ValidateEngineStats(stats)
      assert stats["compile"]                   # compile records present
      assert stats["watchdog"]["beats"] > 0

      code, body = _Get(url("/traces"))
      assert code == 200 and "traceEvents" in json.loads(body)
      assert _Get(url("/healthz"))[0] == 200
      port = eng.status_server.port
    finally:
      eng.Stop()
    assert eng.status_server is None            # Stop() closed the server
    with pytest.raises(Exception):
      _Get(f"http://127.0.0.1:{port}/healthz", timeout=0.5)

  def test_idle_engine_stays_healthy(self, tiny_lm):
    # no traffic is not a stall: the engine loop ticks Idle() while
    # waiting for work, so /healthz stays 200 past the trip window
    from lingvo_tpu.serving import engine as engine_lib
    task, theta = tiny_lm
    wd = watchdog_lib.StallWatchdog(stall_factor=2.0, min_interval_s=0.05)
    eng = engine_lib.ServingLoop(
        task, theta, page_size=4, num_pages=16, max_batch=2,
        max_seq_len=32, prefill_chunk=4, default_max_new=4,
        serve_port=0, watchdog=wd)
    eng.Start()
    try:
      eng.Submit([1, 2, 3], 3).Result(timeout=600)
      time.sleep(0.5)   # >> the ~0.1s no_heartbeat window, but idle
      code, _ = _Get(eng.status_server.Url("/healthz"))
      assert code == 200
      assert wd.Check()["healthy"] is True
    finally:
      eng.Stop()


class TestTrainGoodputMfu:

  def test_short_train_run_publishes_goodput_and_mfu(self, tmp_path):
    import tests.test_executor_hardening as helpers
    from lingvo_tpu.runners import executor as executor_lib
    logdir = str(tmp_path)
    sched, task, _ = helpers._MakeScheduleAndTask(
        logdir, max_steps=10, steps_per_loop=5)
    prev = goodput_lib.Get().Stats()
    scraped = {}
    real_run = sched.Run
    holder = {}

    def _ScrapingRun(state):
      if not scraped:                            # scrape mid-run, once
        code, body = _Get(holder["ex"].status_server.Url("/statusz"))
        scraped["code"], scraped["doc"] = code, json.loads(body)
      return real_run(state)

    sched.Run = _ScrapingRun
    ex = executor_lib.ExecutorTpu(
        helpers._TaskParams(max_steps=10, steps_per_loop=5), logdir,
        schedule=sched, task=task, precompile=True, serve_port=0)
    holder["ex"] = ex
    state = ex.Start()
    assert int(jax.device_get(state.step)) == 10

    # mid-run /statusz: valid doc with the train program's compile records
    assert scraped["code"] == 200
    doc = observe_schema.ValidateStatusz(scraped["doc"])
    assert doc["name"] == "executor"
    recs = doc["stats"]["compile"]["train"]
    assert "step" in recs and recs["step"]["compile_wall_s"] > 0
    assert recs["step"].get("flops", 0) > 0
    # server stopped with the main loop
    assert ex.status_server is None
    # the watchdog auto-created by serve_port beat once per schedule Run
    assert ex.watchdog is not None
    wd = ex.watchdog.Stats()
    assert wd["beats"] >= 2 and wd["healthy"] is True

    # process-global registry: mfu + rate + goodput section all present
    snap = observe.Default().Snapshot()
    assert snap["train/flops_per_step"] > 0
    assert snap["train/peak_flops"] > 0
    assert snap["train/mfu"] >= 0
    assert snap["train/train_steps_per_second"] is not None

    # goodput: this run added productive step time and compile time, and
    # the buckets still partition the wall clock
    cur = goodput_lib.Get().Stats()
    assert cur["step_s"] > prev["step_s"]
    assert cur["compile_s"] > prev["compile_s"]     # precompile tracked
    assert cur["checkpoint_save_s"] >= prev["checkpoint_save_s"]
    bucket_sum = sum(cur[f"{b}_s"] for b in observe_schema.GOODPUT_BUCKETS)
    assert bucket_sum == pytest.approx(cur["wall_s"], rel=1e-3, abs=1e-3)
    assert 0.0 < cur["productive_ratio"] <= 1.0


# -- slow: byte-identical streams with endpoints + scraper live ---------------


@pytest.mark.slow
class TestExporterNonInterference:

  def test_streams_byte_identical_under_scrape_load(self, tiny_lm):
    from lingvo_tpu.serving import engine as engine_lib
    task, theta = tiny_lm
    kw = dict(page_size=4, num_pages=32, max_batch=3, max_seq_len=32,
              prefill_chunk=4, default_max_new=6)
    prompts = [np.random.RandomState(i).randint(1, 63, size=4).tolist()
               for i in range(8)]

    def _RunAll(eng, scrape=False):
      eng.Start()
      stop = threading.Event()
      scraper = None
      if scrape:
        def _Hammer():
          while not stop.is_set():
            _Get(eng.status_server.Url("/metrics"))
            _Get(eng.status_server.Url("/statusz"))
        scraper = threading.Thread(target=_Hammer, daemon=True)
        scraper.start()
      try:
        handles = [eng.Submit(p, 6, seed=i) for i, p in enumerate(prompts)]
        return [h.Result(timeout=600) for h in handles]
      finally:
        stop.set()
        if scraper is not None:
          scraper.join(timeout=10)
        eng.Stop()

    baseline = _RunAll(engine_lib.ServingLoop(task, theta, **kw))
    observed = _RunAll(
        engine_lib.ServingLoop(task, theta, serve_port=0, watchdog=True,
                               **kw), scrape=True)
    assert observed == baseline     # telemetry cannot change the tokens
