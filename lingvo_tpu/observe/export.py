"""Fleet-facing status endpoints: /metrics, /statusz, /traces, /healthz.

Everything PR 12 built is trapped in-process — nothing can be scraped and
no replica can see another. This module opens the door with zero new
dependencies: `StatusServer` runs a stdlib `ThreadingHTTPServer` on a
daemon thread per process and serves

    /metrics   Prometheus text exposition of a MetricsRegistry snapshot
               (counters, gauges, histograms with cumulative buckets;
               string config facts as `_info{value="..."} 1` series)
    /statusz   one JSON document: registry snapshot + Describe() kinds +
               the owner's structured stats (engine Stats() with compile
               records) + jax/build facts — the scrape target
               observe/aggregate.py merges across replicas
    /traces    the existing Chrome trace export (Perfetto-openable)
    /healthz   watchdog-derived liveness: 200 while healthy, 503 after a
               trip. The CHECK runs at scrape time on the HTTP thread —
               a hung step loop cannot self-report, so the scraper's
               thread is the one that must evaluate the trip conditions.

The route table is built from `schema.ENDPOINT_PATHS` and the /statusz
document is validated by `schema.ValidateStatusz`, so endpoint keys can't
drift from the shared schema. Serving stats must never take the service
down: handler errors return 500 with the error string, and the server
binds 127.0.0.1 by default (expose deliberately via host="0.0.0.0").
"""

from __future__ import annotations

import json
import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import jax

from lingvo_tpu.observe import profile as profile_lib
from lingvo_tpu.observe import schema

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def MetricName(name: str) -> str:
  """Registry name -> valid Prometheus metric name (`serving/ttft_s` ->
  `serving_ttft_s`); a leading digit gets an underscore prefix."""
  out = _NAME_RE.sub("_", name)
  if out and out[0].isdigit():
    out = "_" + out
  return out


def _LabelValue(v) -> str:
  return str(v).replace("\\", r"\\").replace('"', r'\"').replace("\n", r"\n")


def _Num(v) -> str:
  """Prometheus sample value formatting (ints stay integral)."""
  if isinstance(v, bool):
    return "1" if v else "0"
  if isinstance(v, int):
    return str(v)
  return repr(float(v))


def KindOf(name: str, describe: dict) -> str:
  """Metric kind for a SNAPSHOT key: exact Describe() entry, else the
  section prefix (`scheduler/queue_depth` -> section `scheduler` ->
  gauge), else gauge."""
  kind = describe.get(name)
  if kind is not None:
    return "gauge" if kind in ("gauge_fn", "section") else kind
  head = name.split("/", 1)[0]
  if describe.get(head) == "section":
    return "gauge"
  return "gauge"


def _IsHistogramSnapshot(v) -> bool:
  return isinstance(v, dict) and "counts" in v and "bounds" in v


def PrometheusText(snapshot: dict, describe: Optional[dict] = None) -> str:
  """A MetricsRegistry Snapshot() as Prometheus text exposition (v0.0.4).

  Numeric values emit as their Describe() kind (counter/gauge); bools as
  0/1 gauges; strings (config facts, `<error: ...>` callback failures) as
  `<name>_info{value="..."} 1`; histogram snapshot dicts as cumulative
  `_bucket{le=...}` series + `_sum` + `_count`; anything else (lists,
  nested dicts) is skipped — it belongs to /statusz, not /metrics."""
  describe = describe or {}
  lines = []
  for name in sorted(snapshot):
    v = snapshot[name]
    mname = MetricName(name)
    if _IsHistogramSnapshot(v):
      lines.append(f"# TYPE {mname} histogram")
      cum = 0
      for bound, n in zip(v["bounds"], v["counts"]):
        cum += n
        lines.append(f'{mname}_bucket{{le="{_Num(bound)}"}} {cum}')
      lines.append(f'{mname}_bucket{{le="+Inf"}} {v["count"]}')
      lines.append(f"{mname}_sum {_Num(v['sum'])}")
      lines.append(f"{mname}_count {v['count']}")
      continue
    if isinstance(v, bool) or isinstance(v, (int, float)):
      lines.append(f"# TYPE {mname} {KindOf(name, describe)}")
      lines.append(f"{mname} {_Num(v)}")
    elif isinstance(v, str):
      lines.append(f"# TYPE {mname}_info gauge")
      lines.append(f'{mname}_info{{value="{_LabelValue(v)}"}} 1')
    elif v is None:
      lines.append(f"# TYPE {mname}_info gauge")
      lines.append(f'{mname}_info{{value="none"}} 1')
    # lists / nested dicts: /statusz carries them
  return "\n".join(lines) + "\n"


def BuildInfo() -> dict:
  """The jax/config facts /statusz carries (schema.BUILD_INFO_KEYS)."""
  import jaxlib
  devs = jax.devices()
  return {
      "jax_version": jax.__version__,
      "jaxlib_version": getattr(jaxlib, "__version__", "unknown"),
      "backend": jax.default_backend(),
      "device_count": jax.device_count(),
      "device_kind": devs[0].device_kind if devs else "unknown",
      "process_index": jax.process_index(),
      "process_count": jax.process_count(),
  }


def _JsonDefault(o):
  """numpy scalars/arrays and anything else stringify instead of raising —
  a weird Stats() value must not 500 the whole /statusz page."""
  try:
    import numpy as np
    if isinstance(o, np.ndarray):
      return o.tolist()
    if isinstance(o, np.generic):
      return o.item()
  except Exception:  # noqa: BLE001
    pass
  return str(o)


class _Httpd(ThreadingHTTPServer):
  daemon_threads = True
  allow_reuse_address = True
  status: "StatusServer" = None


class _Handler(BaseHTTPRequestHandler):

  def log_message(self, *args):  # noqa: D102 - silence per-request stderr
    pass

  def do_GET(self):  # noqa: N802 - http.server API
    status = self.server.status
    path = self.path.split("?", 1)[0]
    fn = status._routes.get(path)
    if fn is None:
      self._Reply(404, "text/plain; charset=utf-8",
                  "not found; endpoints: "
                  + ", ".join(schema.ENDPOINT_PATHS) + "\n")
      return
    try:
      code, ctype, body = fn()
    except Exception as e:  # noqa: BLE001 - stats must not kill the server
      code, ctype, body = 500, "text/plain; charset=utf-8", (
          f"<error: {type(e).__name__}: {e}>\n")
    self._Reply(code, ctype, body)

  def _Reply(self, code: int, ctype: str, body: str):
    data = body.encode("utf-8")
    try:
      self.send_response(code)
      self.send_header("Content-Type", ctype)
      self.send_header("Content-Length", str(len(data)))
      self.end_headers()
      self.wfile.write(data)
    except (BrokenPipeError, ConnectionResetError):
      pass  # scraper went away mid-reply


class StatusServer:
  """A per-process status HTTP server over one MetricsRegistry.

  port=0 binds an ephemeral port (tests, multi-engine processes); the
  bound port is `self.port` and `Url(path)` builds scrape URLs.
  statusz_fn: zero-arg callable returning the owner's structured stats
  (engine `Stats()`), spliced into /statusz as `stats`. trace: a
  TraceRecorder for /traces (404 without one). watchdog: a StallWatchdog
  — /healthz runs its `Check()` at scrape time and flips to 503 on a
  trip (200 `{"healthy": true, "watchdog": false}` without one).
  """

  def __init__(self, port: int = 0, host: str = "127.0.0.1", *,
               registry=None, name: str = "", statusz_fn=None, trace=None,
               watchdog=None):
    self._registry = registry
    self.name = name
    self._statusz_fn = statusz_fn
    self._trace = trace
    self._watchdog = watchdog
    self._routes = {
        "/metrics": self._Metrics,
        "/statusz": self._Statusz,
        "/traces": self._Traces,
        "/healthz": self._Healthz,
    }
    assert set(self._routes) == set(schema.ENDPOINT_PATHS), (
        "route table drifted from schema.ENDPOINT_PATHS")
    self._httpd = _Httpd((host, port), _Handler)
    self._httpd.status = self
    self.host = self._httpd.server_address[0]
    self.port = self._httpd.server_address[1]
    self._thread: Optional[threading.Thread] = None

  def Start(self) -> "StatusServer":
    if self._thread is None:
      self._thread = threading.Thread(
          target=self._httpd.serve_forever, daemon=True,
          name=f"status-server-{self.name or self.port}")
      self._thread.start()
    return self

  def Stop(self):
    if self._thread is not None:
      self._httpd.shutdown()
      self._thread.join(timeout=5.0)
      self._thread = None
    self._httpd.server_close()

  def Url(self, path: str = "/metrics") -> str:
    return f"http://{self.host}:{self.port}{path}"

  # -- endpoint bodies (run on the HTTP threads) ------------------------------

  def _Metrics(self):
    if self._registry is None:
      return 404, "text/plain; charset=utf-8", "no registry\n"
    body = PrometheusText(self._registry.Snapshot(),
                          self._registry.Describe())
    return 200, "text/plain; version=0.0.4; charset=utf-8", body

  def Statusz(self) -> dict:
    """The /statusz document (schema-validated), also used in-process."""
    doc = {
        "name": self.name,
        "build": BuildInfo(),
        "snapshot": (self._registry.Snapshot()
                     if self._registry is not None else {}),
        "describe": (self._registry.Describe()
                     if self._registry is not None else {}),
        "stats": self._statusz_fn() if self._statusz_fn is not None else None,
        "startup": profile_lib.Startup().Document(),
    }
    if self._watchdog is not None:
      doc["watchdog"] = self._watchdog.Stats()
    return schema.ValidateStatusz(doc)

  def _Statusz(self):
    body = json.dumps(self.Statusz(), default=_JsonDefault, indent=1)
    return 200, "application/json; charset=utf-8", body + "\n"

  def _Traces(self):
    if self._trace is None:
      return 404, "text/plain; charset=utf-8", "tracing disabled\n"
    body = json.dumps(self._trace.ChromeTrace(), default=_JsonDefault)
    return 200, "application/json; charset=utf-8", body + "\n"

  def _Healthz(self):
    if self._watchdog is None:
      body = json.dumps({"healthy": True, "watchdog": False})
      return 200, "application/json; charset=utf-8", body + "\n"
    stats = self._watchdog.Check()
    code = 200 if stats["healthy"] else 503
    return code, "application/json; charset=utf-8", (
        json.dumps(stats, default=_JsonDefault) + "\n")
