"""Live ops server: the per-rank HTTP plane supervisors actually poll.

Eight PRs of in-process instrumentation (telemetry registry, diagnostics
spans, flight recorder, postmortem bundles) were all dump-to-file and
post-hoc. ``opsd`` turns them into a live, per-process control/metrics
plane — the thing a load balancer health-checks, a Prometheus scrapes,
and an elastic-training supervisor polls (docs/observability.md §5;
the TensorFlow paper's long-running training/serving-fleet framing):

  GET  /metrics          Prometheus scrape of the telemetry registry
  GET  /healthz          liveness: the process (and its ops thread) is up
  GET  /readyz           readiness: no ongoing watchdog stall, every
                         registered serving engine admitting (503 + the
                         failing checks otherwise)
  GET  /flight?n=N       live flight-ring tail as JSON (newest N);
                         &kind=PREFIX filters by event-kind prefix
                         (kind=serve pulls only serving events)
  GET  /traces?n=N       newest N finished request traces (reqtrace.py:
                         phase spans, batch links, SLO table, per-phase
                         summary); &class= / &model= filter
  GET  /steps            step-tracer phase table + last-step/step-rate
  GET  /identity         (job_id, rank, world) + pid/host/port — stamped
                         by kvstore.tpu_dist at collective init
  POST /postmortem       write a postmortem bundle NOW, return its path
  POST /profile?ms=N     capture a jax.profiler trace for N ms, return
                         the trace directory

Opt-in and cheap: with ``MXTPU_OPS_PORT`` unset no thread or socket is
ever created; with it set, one stdlib ``ThreadingHTTPServer`` runs on a
daemon thread named ``mxtpu-opsd`` (exempt from the DataLoader fork
heuristic like every framework service thread). GET handlers only read
snapshot APIs that already exist for postmortems — they take no jax
locks and never touch the device, so a 10 Hz scraper cannot retrace,
stall, or perturb a donated whole-step training loop. The POST
endpoints mutate (bundle writes, profiler sessions) and can be gated
with ``MXTPU_OPS_TOKEN`` (bearer token).

Fleet view: ``tools/fleetctl.py`` polls N ranks' servers into one
straggler-annotated table and can fan ``POST /postmortem`` out to every
rank for a ``tools/blackbox.py`` merge.

Fork/exit safety: an ``os.fork`` child (DataLoader workers) inherits
the listening socket fd but not the server thread — the at-fork hook
closes the child's fd and clears the singleton so the child neither
holds the port nor believes a server runs. ``atexit`` stops the server
on interpreter shutdown so the port is released before teardown.
"""
from __future__ import annotations

import json
import os
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

__all__ = ["OpsServer", "start", "stop", "server", "start_from_env"]

_singleton = [None]   # the env-started per-process server
_lock = threading.Lock()

_PROFILE_MAX_MS = 60_000


def _env_get(name, default):
    try:
        from .. import env as _env

        if name in _env.all_vars():
            return _env.get(name)
    except Exception:
        pass
    raw = os.environ.get(name)
    return default if raw is None else raw


# ---------------------------------------------------------------------------
# endpoint payload builders (pure snapshot reads; shared with tests)
# ---------------------------------------------------------------------------


def health_payload():
    """Liveness: the process is up and its Python side can answer."""
    from ..diagnostics import spans as _spans
    from . import flight as _flight

    return {
        "status": "ok",
        "pid": os.getpid(),
        "time": time.time(),
        "step": _spans.current_step(),
        "identity": _flight.identity(),
    }


def readiness_payload():
    """Readiness checks: ``ready`` is False while a watchdog guard has
    fired and is still open (an ongoing stall) or while any registered
    serving engine would shed/refuse a submit right now. Engines are
    read from ``serving.REGISTRY`` — register yours there to have the
    front door health-check it."""
    checks = {}
    ready = True
    try:
        from ..diagnostics import watchdog as _watchdog

        stalled = _watchdog.stalled_sites()
        checks["watchdog"] = {
            "ok": not stalled,
            "stalled_sites": stalled,
            "fire_count": _watchdog.fire_count(),
        }
        ready &= not stalled
    except Exception as e:
        checks["watchdog"] = {"ok": True, "error": repr(e)}
    try:
        import sys

        serving = sys.modules.get("mxnet_tpu.serving")
        engines = {}
        if serving is not None:
            for name in serving.REGISTRY.names():
                eng = serving.REGISTRY.get(name)
                state = eng.admission_state()
                engines[name] = {
                    "admission": state,
                    "queue_depth": eng.queue_depth(),
                    "max_queue": eng.max_queue,
                    "started": eng.started,
                }
                ready &= state == "ok"
        checks["serving"] = {
            "ok": all(e["admission"] == "ok" for e in engines.values()),
            "engines": engines,
        }
    except Exception as e:
        checks["serving"] = {"ok": True, "error": repr(e)}
    try:
        from . import reqtrace

        # a class burning through its error budget drops this replica
        # from rotation (front doors poll /readyz); recovery is
        # automatic once the rolling window sheds the violations
        burning = reqtrace.slo_burning()
        checks["slo"] = {
            "ok": not burning,
            "burning": burning,
            "status": reqtrace.slo_status(),
        }
        ready &= not burning
    except Exception as e:
        checks["slo"] = {"ok": True, "error": repr(e)}
    return {"ready": bool(ready), "checks": checks}


def steps_payload():
    """The step tracer's live view: per-step phase table, last step, and
    the step-rate gauges a fleet poller derives straggler skew from."""
    from ..diagnostics import spans as _spans

    out = {
        "last_step": _spans.current_step(),
        "step_table": {str(k): v for k, v in _spans.step_table().items()},
    }
    try:
        from ..telemetry import instruments as ti

        st = ti.step_time_seconds
        out["steps_observed"] = st.count
        out["step_time_ms_avg"] = \
            round(st.sum / st.count * 1e3, 3) if st.count else None
        out["examples_per_second"] = ti.examples_per_second.value
        out["step_dispatches"] = {
            lv[0]: c.value for lv, c in ti.step_dispatch_total.series()}
    except Exception as e:
        out["telemetry_error"] = repr(e)
    return out


def identity_payload(srv=None):
    from . import flight as _flight

    out = dict(_flight.identity())
    out["pid"] = os.getpid()
    if srv is not None:
        out["host"], out["port"] = srv.host, srv.port
        out["started_at"] = srv.started_at
    return out


def flight_payload(n=256, kind=None):
    from . import flight as _flight

    evs = _flight.events(kind=kind)
    n = max(0, int(n))
    return {
        "identity": _flight.identity(),
        "capacity": _flight.capacity(),
        "kind": kind,
        "total": len(evs),
        "events": evs[-n:] if n else [],
    }


def traces_payload(n=32, cls=None, model=None):
    """Finished request traces + batch causality links + the live SLO
    table and per-phase latency breakdown (reqtrace.py). ``n=0`` keeps
    just the summaries — what fleetctl polls per rank."""
    from . import flight as _flight
    from . import reqtrace

    recs = reqtrace.traces(cls=cls, model=model)
    n = max(0, int(n))
    # decode-sequence traces carry per-token spans; summarize them so
    # TTFT / inter-token behavior reads off /traces without digging
    # through span lists (decode/engine.py stamps prefill + token)
    decode = {"sequences": 0, "tokens": 0}
    ttfts = []
    for r in recs:
        spans = r.get("spans", ())
        toks = sum(1 for sp in spans if sp["phase"] == "token")
        if not toks:
            continue
        decode["sequences"] += 1
        decode["tokens"] += toks
        for sp in spans:
            if sp["phase"] == "token":
                # first token span closes at its stamp: TTFT = t0 + dur
                # relative to trace start
                ttfts.append((sp["t0"] + sp["dur"] - r["t0"]) * 1e3)
                break
    if ttfts:
        ttfts.sort()
        decode["ttft_p50_ms"] = round(ttfts[len(ttfts) // 2], 3)
        decode["ttft_max_ms"] = round(ttfts[-1], 3)
    return {
        "identity": _flight.identity(),
        "sample_rate": reqtrace.sample_rate(),
        "capacity": reqtrace.ring_capacity(),
        "class": cls,
        "model": model,
        "total": len(recs),
        "traces": recs[-n:] if n else [],
        "batches": reqtrace.batches(n),
        "phases": reqtrace.phase_summary(),
        "decode": decode,
        "slo": reqtrace.slo_status(),
    }


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    server_version = "mxtpu-opsd"

    # BaseHTTPRequestHandler logs every request to stderr; a 10 Hz
    # scraper would bury real output
    def log_message(self, fmt, *args):  # noqa: ARG002
        pass

    @property
    def ops(self):
        return self.server._ops  # the owning OpsServer

    def _send(self, code, body, content_type="application/json"):
        if isinstance(body, (dict, list)):
            body = json.dumps(body, default=str)
        payload = body.encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def _authorized(self):
        token = str(_env_get("MXTPU_OPS_TOKEN", "") or "")
        if not token:
            return True
        got = self.headers.get("Authorization", "")
        return got == f"Bearer {token}"

    def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler contract
        url = urlparse(self.path)
        q = parse_qs(url.query)
        try:
            if url.path == "/metrics":
                from ..telemetry import prometheus_text
                from ..telemetry.promparse import CONTENT_TYPE

                self._send(200, prometheus_text(),
                           content_type=CONTENT_TYPE)
            elif url.path == "/healthz":
                self._send(200, health_payload())
            elif url.path == "/readyz":
                p = readiness_payload()
                self._send(200 if p["ready"] else 503, p)
            elif url.path == "/steps":
                self._send(200, steps_payload())
            elif url.path == "/identity":
                self._send(200, identity_payload(self.ops))
            elif url.path == "/flight":
                n = int(q.get("n", ["256"])[0])
                kind = q.get("kind", [None])[0]
                self._send(200, flight_payload(n, kind=kind))
            elif url.path == "/traces":
                n = int(q.get("n", ["32"])[0])
                cls = q.get("class", [None])[0]
                model = q.get("model", [None])[0]
                self._send(200, traces_payload(n, cls=cls, model=model))
            elif url.path == "/":
                self._send(200, {
                    "server": "mxtpu-opsd",
                    "endpoints": ["/metrics", "/healthz", "/readyz",
                                  "/steps", "/identity", "/flight",
                                  "/traces",
                                  "POST /postmortem", "POST /profile"],
                })
            else:
                self._send(404, {"error": f"no endpoint {url.path!r}"})
        except Exception as e:  # a broken section must answer, not hang
            self._send(500, {"error": repr(e)})

    def do_POST(self):  # noqa: N802
        url = urlparse(self.path)
        q = parse_qs(url.query)
        if not self._authorized():
            self._send(401, {"error": "MXTPU_OPS_TOKEN required "
                                      "(Authorization: Bearer <token>)"})
            return
        try:
            if url.path == "/postmortem":
                from . import postmortem

                path = postmortem.dump(reason="opsd", sync=True)
                self._send(200, {"path": os.path.abspath(path)})
            elif url.path == "/profile":
                ms = float(q.get("ms", ["1000"])[0])
                self._send(200, self.ops.capture_profile(ms))
            else:
                self._send(404, {"error": f"no endpoint {url.path!r}"})
        except Exception as e:
            self._send(500, {"error": repr(e)})


class OpsServer:
    """One live ops endpoint: a ThreadingHTTPServer on a daemon thread.

    ``port=0`` binds an ephemeral port (tests, multi-engine bring-up);
    the bound port is ``self.port``. The server is independent of the
    module singleton, so a front-door process can run several.
    """

    def __init__(self, port=None, host=None):
        if port is None:
            port = int(_env_get("MXTPU_OPS_PORT", 0) or 0)
        if host is None:
            host = str(_env_get("MXTPU_OPS_HOST", "127.0.0.1")
                       or "127.0.0.1")
        self._httpd = ThreadingHTTPServer((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        self._httpd._ops = self
        self.host, self.port = self._httpd.server_address[:2]
        self.started_at = time.time()
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="mxtpu-opsd",
            daemon=True, kwargs={"poll_interval": 0.1})
        self._profile_lock = threading.Lock()
        self._stopped = False

    @property
    def url(self):
        return f"http://{self.host}:{self.port}"

    def start(self):
        self._thread.start()
        try:
            from . import flight

            flight.record("opsd_start", host=self.host, port=self.port)
        except Exception:
            pass
        return self

    @property
    def running(self):
        return self._thread.is_alive() and not self._stopped

    def stop(self):
        """Shut the listener down and release the port (idempotent)."""
        if self._stopped:
            return self
        self._stopped = True
        try:
            self._httpd.shutdown()
        except Exception:
            pass
        try:
            self._httpd.server_close()
        except Exception:
            pass
        if self._thread.is_alive() and \
                self._thread is not threading.current_thread():
            self._thread.join(timeout=5)
        try:
            from . import flight

            flight.record("opsd_stop", port=self.port)
        except Exception:
            pass
        return self

    def _close_inherited_socket(self):
        # after os.fork the CHILD holds a copy of the listening fd but
        # no server thread; close the copy so the child doesn't keep the
        # port open (the parent's listener is unaffected)
        self._stopped = True
        try:
            self._httpd.socket.close()
        except Exception:
            pass

    def capture_profile(self, ms):
        """On-demand ``jax.profiler`` capture: trace for ``ms`` wall
        milliseconds into a fresh directory under MXTPU_FLIGHTREC_DIR,
        return ``{"dir", "ms"}``. One capture at a time — overlapping
        requests get 409-shaped errors rather than corrupt traces."""
        ms = max(1.0, min(float(ms), float(_PROFILE_MAX_MS)))
        if not self._profile_lock.acquire(blocking=False):
            raise RuntimeError("a profile capture is already running")
        try:
            import jax

            base = str(_env_get("MXTPU_FLIGHTREC_DIR", ".") or ".")
            out = os.path.join(
                base, f"opsd_profile_{int(time.time() * 1e3)}")
            os.makedirs(out, exist_ok=True)
            jax.profiler.start_trace(out)
            try:
                time.sleep(ms / 1e3)
            finally:
                jax.profiler.stop_trace()
            try:
                from . import flight

                flight.record("opsd_profile", dir=out, ms=ms)
            except Exception:
                pass
            return {"dir": os.path.abspath(out), "ms": ms}
        finally:
            self._profile_lock.release()


# ---------------------------------------------------------------------------
# per-process singleton (the MXTPU_OPS_PORT path)
# ---------------------------------------------------------------------------


def server():
    """The env-started per-process server, or None."""
    return _singleton[0]


def start(port=None, host=None):
    """Start (or return) the per-process ops server. Idempotent; the
    first call wins the port. Registers the atexit stop."""
    with _lock:
        srv = _singleton[0]
        if srv is not None and srv.running:
            return srv
        srv = OpsServer(port=port, host=host).start()
        _singleton[0] = srv

        import atexit

        atexit.register(_atexit_stop)
        return srv


def stop():
    """Stop the per-process server (no-op when none runs)."""
    with _lock:
        srv = _singleton[0]
        _singleton[0] = None
    if srv is not None:
        srv.stop()
    return srv


def _atexit_stop():
    try:
        stop()
    except Exception:
        pass


def start_from_env():
    """The import-time hook: start iff ``MXTPU_OPS_PORT`` is set and
    non-zero. With it unset this touches nothing — no thread, no
    socket, no jax import."""
    raw = os.environ.get("MXTPU_OPS_PORT")
    if not raw:
        return None
    try:
        port = int(raw)
    except ValueError:
        return None
    if port <= 0:
        return None
    try:
        return start(port=port)
    except OSError:
        # the port is taken (a sibling rank on the same host, a stale
        # process) — a dead ops plane must never kill training
        return None


def _after_fork_in_child():
    srv = _singleton[0]
    _singleton[0] = None
    if srv is not None:
        srv._close_inherited_socket()


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_after_fork_in_child)
