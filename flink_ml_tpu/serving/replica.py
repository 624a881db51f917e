"""Replica subprocess lifecycle + wire protocol for the replica router.

One process is a scaling ceiling no matter how fast the chip path gets
(ROADMAP item 2): this module is the *execution substrate* half of the
scale-out split — everything needed to run one ``ModelServer`` as a
supervised child process and talk to it from a routing parent:

* **the child** (``python -m flink_ml_tpu.serving.replica``) loads a
  saved pipeline, brings up a ``ModelServer`` with telemetry on an
  ephemeral port (``telemetry_port=0``), and serves a tiny loopback
  data-plane HTTP endpoint in front of it: ``POST /submit`` forwards a
  request table into ``ModelServer.submit`` (the replica's dispatcher
  coalesces concurrent forwards into fused batches exactly as it does
  in-process callers), ``POST /deploy`` drives the round-10 zero-downtime
  swap contract (``versioning.py``: load -> verify -> pre-warm -> atomic
  swap; a corrupt artifact raises and the old version keeps serving),
  ``GET /healthz`` answers liveness.  Both bound ports are published for
  the parent: the data address through ``--address-file`` and the
  telemetry address through ``FMT_TELEMETRY_PORT_FILE`` (ISSUE 13's
  ephemeral-port discovery fix) — each written atomically
  (:func:`~flink_ml_tpu.obs.telemetry.write_port_file`);

* **the parent-side handles**: :class:`ReplicaProcess` spawns, boots,
  supervises, and stops one child (handshake with a boot deadline, log
  capture to the replica workdir, ``alive()``/``poll_dead()`` for the
  router's crash detection, SIGTERM-then-SIGKILL stop);
  :class:`ReplicaClient` is the matching wire client — ``submit`` returns
  a :class:`~flink_ml_tpu.serving.batcher.ServeResult` or re-raises the
  replica's reason-coded :class:`~flink_ml_tpu.serving.errors.
  ServerOverloadedError` exactly as an in-process caller would see it,
  ``probe`` scrapes ``/readyz`` + ``/metrics`` (through the STRICT
  :func:`~flink_ml_tpu.obs.telemetry.parse_openmetrics`, never a trusting
  regex) into the health view the router balances on.

Wire format: pickled numpy column payloads over loopback HTTP.  This is
*trusted same-user subprocess IPC* — both ends are this package, spawned
by this package, bound to 127.0.0.1 — not a public protocol; the framing
exists to cross a process boundary bit-exactly (results must be
bit-identical to a solo in-process ``transform``), not to be spoken by
strangers.  Tables travel as ``(field_names, field_types, column
buffers)`` so the per-table pack cache (which may pin device buffers)
never crosses the boundary.
"""

from __future__ import annotations

import io
import json
import os
import pickle
import signal
import subprocess
import sys
import tempfile
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional

from flink_ml_tpu.obs import trace
from flink_ml_tpu.serving.batcher import ServeResult
from flink_ml_tpu.serving.errors import (
    SHED_SHUTDOWN,
    ServerClosedError,
    ServerOverloadedError,
)

__all__ = [
    "ReplicaClient",
    "ReplicaProcess",
    "ReplicaRemoteError",
    "ReplicaUnreachableError",
    "decode_table",
    "encode_table",
    "main",
]


class ReplicaUnreachableError(RuntimeError):
    """The replica's endpoint did not answer (connection refused/reset,
    timeout, dead socket): the process is gone or wedged.  The router
    treats this as a replica failure — retry the request elsewhere, eject
    and respawn the replica — never as a request failure."""


class ReplicaRemoteError(RuntimeError):
    """The replica answered with a real (non-shed) failure: the transform
    raised, a deploy was refused.  ``remote_type`` names the exception
    class inside the replica (``ModelIntegrityError``, ``ValueError``,
    ...) so supervisors can classify without parsing prose."""

    def __init__(self, remote_type: str, detail: str):
        super().__init__(f"{remote_type}: {detail}")
        self.remote_type = remote_type
        self.detail = detail


# -- wire encoding ------------------------------------------------------------


def encode_table(table) -> tuple:
    """One table as ``(names, types, {name: column buffer})`` — schema and
    raw columns only, so the pickle never drags the table's device-layout
    pack cache (or anything else process-local) across the boundary."""
    names = list(table.schema.field_names)
    return (
        names,
        list(table.schema.field_types),
        {n: table.col(n) for n in names},
    )


def decode_table(wire: tuple):
    """Rebuild a :class:`~flink_ml_tpu.table.table.Table` from
    :func:`encode_table` output, buffer-exact (no re-coercion: the
    columns were valid buffers on the sending side and must stay
    bit-identical for the router's parity contract)."""
    from flink_ml_tpu.table.schema import Schema
    from flink_ml_tpu.table.table import Table

    names, types, cols = wire
    return Table(Schema(list(names), list(types)), dict(cols))


def _dumps(obj: dict) -> bytes:
    return pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)


def _loads(data: bytes) -> dict:
    return pickle.loads(data)


# -- the in-child data-plane endpoint -----------------------------------------


class _DataHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer carrying the wrapped ``ModelServer`` for its
    handler threads (http.server hands handlers only the server object)."""

    daemon_threads = True
    model_server = None  # set by ReplicaDataServer before serving


class _DataHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"  # keep-alive: one socket per router lane

    def _reply(self, code: int, payload: dict) -> None:
        body = _dumps(payload)
        self.send_response(code)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _read_body(self) -> dict:
        length = int(self.headers.get("Content-Length", "0"))
        return _loads(self.rfile.read(length))

    def do_GET(self) -> None:  # noqa: N802 - http.server contract
        if self.path.split("?", 1)[0] == "/healthz":
            # ``ts`` feeds the router's NTP-style clock probe: the fleet
            # stitcher corrects each replica's spans onto one timeline
            body = json.dumps({"ok": True, "pid": os.getpid(),
                               "ts": time.time()}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            self._reply(404, {"type": "NotFound", "detail": self.path})

    def do_POST(self) -> None:  # noqa: N802 - http.server contract
        path = self.path.split("?", 1)[0]
        try:
            if path == "/submit":
                self._submit(self._read_body())
            elif path == "/deploy":
                self._deploy(self._read_body())
            else:
                self._reply(404, {"type": "NotFound", "detail": path})
        except BrokenPipeError:  # caller hung up mid-response
            pass
        except Exception as exc:  # noqa: BLE001 - the wire carries it
            try:
                self._reply(500, {"type": type(exc).__name__,
                                  "detail": str(exc)})
            except Exception:  # noqa: BLE001 - socket already gone
                pass

    def _submit(self, payload: dict) -> None:
        server = self.server.model_server
        table = decode_table(payload["table"])
        remote = payload.get("trace") or {}
        try:
            # adopt the router's trace context for this handler thread:
            # the server's request root then JOINS the routed trace,
            # parented under the router's dispatch span
            with trace.adopt(remote.get("trace_id"),
                             remote.get("parent_span_id", "")):
                result = server.predict(
                    table,
                    deadline_ms=payload.get("deadline_ms"),
                    timeout=payload.get("timeout_s", 120.0),
                    tenant=payload.get("tenant"),
                )
        except ServerOverloadedError as exc:
            # the shed travels as DATA, reason code intact: the router's
            # retry classification consumes the code, not the prose
            self._reply(503, {"shed": exc.reason, "detail": str(exc),
                              "trace_id": exc.trace_id})
            return
        except ServerClosedError as exc:
            self._reply(503, {"shed": SHED_SHUTDOWN, "detail": str(exc),
                              "trace_id": None})
            return
        self._reply(200, {
            "table": encode_table(result.table),
            "quarantine": {name: encode_table(t)
                           for name, t in result.quarantine.items()},
            "version": result.version,
            # SUCCESSES carry the trace id too (sheds always did): a
            # caller can correlate any response with its waterfall
            "trace_id": result.trace_id,
        })

    def _deploy(self, payload: dict) -> None:
        server = self.server.model_server
        # the round-10 swap contract does the heavy lifting: a failure
        # here (corrupt artifact, broken warmup) left the old version
        # serving, and the 500 carries the loader's diagnostic type
        server.deploy(payload["path"], payload["version"])
        self._reply(200, {"version": server.active_version})

    def log_message(self, *args) -> None:  # silence per-request stderr
        pass


class ReplicaDataServer:
    """The replica-side data-plane endpoint: bind loopback-ephemeral,
    serve on daemon threads, stop cleanly.  Separate from the telemetry
    endpoint on purpose — probes must keep answering while the data plane
    is saturated, and GET-only telemetry never grows a POST surface."""

    def __init__(self, model_server, host: str = "127.0.0.1",
                 port: int = 0):
        self._host = host
        self._httpd = _DataHTTPServer((host, port), _DataHandler)
        self._httpd.model_server = model_server
        self._thread: Optional[threading.Thread] = None

    @property
    def host(self) -> str:
        return self._host

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def address(self) -> str:
        return f"{self._host}:{self.port}"

    def start(self) -> "ReplicaDataServer":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._httpd.serve_forever, name="fmt-replica-data",
                daemon=True, kwargs={"poll_interval": 0.1},
            )
            self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        thread, self._thread = self._thread, None
        if thread is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            thread.join(timeout=timeout)


# -- the parent-side wire client ----------------------------------------------


class ReplicaClient:
    """HTTP client for one replica's data + telemetry endpoints.

    Data-plane POSTs ride a PERSISTENT per-thread connection (the
    router's dispatch lanes each keep one socket open to each replica):
    per-request TCP handshakes — and the handler thread the replica's
    ThreadingHTTPServer would spawn per connection — are paid once per
    lane, not once per request.  A keep-alive socket the replica closed
    between requests (restart, idle timeout) retries ONCE on a fresh
    connection before the failure is declared a dead replica."""

    def __init__(self, serve_address: str,
                 telemetry_address: Optional[str] = None):
        self.serve_address = serve_address
        self.telemetry_address = telemetry_address
        self._local = threading.local()

    def _connection(self, timeout_s: float):
        """This thread's persistent connection (fresh one on first use
        or after :meth:`_drop_connection`); returns ``(conn, reused)``."""
        import http.client

        conn = getattr(self._local, "conn", None)
        reused = conn is not None
        if conn is None:
            host, _, port = self.serve_address.rpartition(":")
            conn = http.client.HTTPConnection(host, int(port),
                                              timeout=timeout_s)
            self._local.conn = conn
        if conn.sock is not None:
            conn.sock.settimeout(timeout_s)
        else:
            conn.timeout = timeout_s
        return conn, reused

    def _drop_connection(self) -> None:
        conn = getattr(self._local, "conn", None)
        self._local.conn = None
        if conn is not None:
            try:
                conn.close()
            except Exception:  # noqa: BLE001 - already broken
                pass

    def _post(self, path: str, payload: dict, timeout_s: float) -> dict:
        import http.client

        body = _dumps(payload)
        last_exc: Optional[BaseException] = None
        for attempt in (1, 2):
            conn, reused = self._connection(timeout_s)
            try:
                conn.request("POST", path, body, {
                    "Content-Type": "application/octet-stream",
                })
                resp = conn.getresponse()
                data = resp.read()
                status = resp.status
            except (ConnectionError, TimeoutError,
                    http.client.HTTPException, OSError) as exc:
                # a half-written response (the replica died mid-reply)
                # parses as an HTTPException — same verdict as a refused
                # connection.  A REUSED socket failing cleanly is the
                # keep-alive race (the peer closed it between requests):
                # one retry on a fresh connection, then it's a dead peer.
                self._drop_connection()
                last_exc = exc
                if reused and attempt == 1 and not isinstance(
                        exc, TimeoutError):
                    continue
                break
            if status == 200:
                return _loads(data)
            try:
                answer = _loads(data)
            except Exception:  # noqa: BLE001 - a mangled error is a dead peer
                self._drop_connection()
                raise ReplicaUnreachableError(
                    f"replica {self.serve_address} returned undecodable "
                    f"error body (HTTP {status})"
                ) from None
            if "shed" in answer:
                raise ServerOverloadedError(
                    answer["shed"], answer.get("detail", ""),
                    trace_id=answer.get("trace_id"),
                ) from None
            raise ReplicaRemoteError(
                answer.get("type", "Unknown"), answer.get("detail", "")
            ) from None
        raise ReplicaUnreachableError(
            f"replica {self.serve_address} unreachable: {last_exc}"
        ) from last_exc

    def submit(self, table, deadline_ms: Optional[float] = None,
               timeout_s: float = 120.0,
               trace_ctx: Optional[tuple] = None,
               tenant: Optional[str] = None) -> ServeResult:
        """Forward one request; returns the replica's
        :class:`ServeResult` (tables bit-identical to an in-process
        serve) or raises the replica's reason-coded shed /
        :class:`ReplicaRemoteError` / :class:`ReplicaUnreachableError`.

        ``trace_ctx`` is an optional ``(trace_id, parent_span_id)`` pair
        shipped in the payload so the replica records its spans inside
        the ROUTER's trace (``trace.adopt`` on the far side).
        ``tenant`` is the multi-tenant routing key (ISSUE 20) — omitted
        from the payload when None, so the wire format stays readable by
        pre-tenant replicas."""
        payload = {
            "table": encode_table(table), "deadline_ms": deadline_ms,
            "timeout_s": timeout_s,
        }
        if tenant is not None:
            payload["tenant"] = tenant
        if trace_ctx:
            payload["trace"] = {"trace_id": trace_ctx[0],
                                "parent_span_id": trace_ctx[1]}
        answer = self._post("/submit", payload, timeout_s=timeout_s + 10.0)
        return ServeResult(
            table=decode_table(answer["table"]),
            quarantine={name: decode_table(wire)
                        for name, wire in answer["quarantine"].items()},
            version=answer["version"],
            trace_id=answer.get("trace_id"),
        )

    def deploy(self, path: str, version: str,
               timeout_s: float = 600.0) -> str:
        """Drive the replica's zero-downtime swap; returns the active
        version after the swap.  A failed deploy surfaces as
        :class:`ReplicaRemoteError` naming the replica-side exception
        (``ModelIntegrityError`` for a corrupt artifact) — the replica
        keeps serving its old version (the versioning.py contract)."""
        answer = self._post("/deploy", {"path": path, "version": version},
                            timeout_s=timeout_s)
        return answer["version"]

    def healthz(self, timeout_s: float = 2.0) -> dict:
        import urllib.request

        try:
            with urllib.request.urlopen(
                f"http://{self.serve_address}/healthz", timeout=timeout_s
            ) as resp:
                return json.loads(resp.read().decode())
        except Exception as exc:  # noqa: BLE001 - any failure = unreachable
            raise ReplicaUnreachableError(
                f"replica {self.serve_address} healthz failed: {exc}"
            ) from exc

    def clock_probe(self, timeout_s: float = 2.0) -> dict:
        """NTP-style clock-offset estimate for this replica's process:
        ``{"pid", "offset_s", "rtt_s"}``, where ``offset_s`` is the
        replica wall clock minus ours, measured against the probe RTT's
        midpoint (the error bound is the RTT asymmetry — loopback
        microseconds, far below span widths).  The router feeds this to
        :func:`flink_ml_tpu.obs.trace.note_clock_offset` so the fleet
        stitcher lands every process's spans on ONE timeline."""
        t0 = time.time()
        body = self.healthz(timeout_s=timeout_s)
        rtt = time.time() - t0
        server_ts = float(body.get("ts") or 0.0)
        offset = server_ts - (t0 + rtt / 2.0) if server_ts else 0.0
        return {"pid": int(body.get("pid") or 0), "offset_s": offset,
                "rtt_s": rtt}

    def probe(self, timeout_s: float = 2.0, depth: bool = True) -> dict:
        """One health-poll sample off the replica's telemetry plane:
        ``{"ready": bool, "reasons": [str, ...], "queue_depth": float,
        "burn_rates": {slo_name: rate}}``.

        ``/readyz`` gives the reason-coded verdict (``breaker_open``,
        ``memory_pressure``, ``slo_burning``, ``drift``,
        ``deploy_in_progress``, ``queue_saturated``, ...); ``/metrics``
        — validated through the STRICT OpenMetrics parser, so a
        half-written scrape can never feed the balancer garbage — gives
        the queue depth power-of-two-choices compares.  ``depth=False``
        skips the metrics scrape (rendering a full registry exposition
        is the expensive half of a probe; the router refreshes depth on
        a slower cadence than readiness) — the sample then carries no
        ``queue_depth`` key so the caller keeps its last value."""
        import http.client
        import urllib.error
        import urllib.request

        from flink_ml_tpu.obs import telemetry

        if not self.telemetry_address:
            raise ReplicaUnreachableError(
                f"replica {self.serve_address} has no telemetry address"
            )
        base = f"http://{self.telemetry_address}"
        try:
            try:
                with urllib.request.urlopen(f"{base}/readyz",
                                            timeout=timeout_s) as resp:
                    ready_body = resp.read().decode()
            except urllib.error.HTTPError as exc:
                if exc.code != 503:
                    raise
                ready_body = exc.read().decode()  # unready IS an answer
            metrics_text = None
            if depth:
                with urllib.request.urlopen(f"{base}/metrics",
                                            timeout=timeout_s) as resp:
                    metrics_text = resp.read().decode()
        except (urllib.error.URLError, ConnectionError, TimeoutError,
                http.client.HTTPException, OSError) as exc:
            # HTTPException covers a peer killed MID-RESPONSE (empty
            # status line): same verdict as a refused connection
            raise ReplicaUnreachableError(
                f"replica telemetry {self.telemetry_address} "
                f"unreachable: {exc}"
            ) from exc
        try:
            verdict = json.loads(ready_body)
            samples = (telemetry.parse_openmetrics(metrics_text)
                       if metrics_text is not None else None)
        except ValueError as exc:
            # a torn scrape (process dying mid-write) must read as
            # unreachable, never crash the poll loop
            raise ReplicaUnreachableError(
                f"replica telemetry {self.telemetry_address} returned "
                f"an unparseable scrape: {exc}"
            ) from exc
        out = {
            "ready": bool(verdict.get("ready")),
            "reasons": sorted({r.get("reason", "unknown")
                               for r in verdict.get("reasons", [])}),
        }
        if samples is not None:
            out["queue_depth"] = float(
                samples.get("fmt_serving_queue_depth", 0.0))
            # per-SLO burn rates off the same strict scrape: the gauge
            # family ``slo.burn_rate.<name>`` renders as
            # ``fmt_slo_burn_rate_<name>`` — the autoscaler's scale-up
            # signal rides the probe the router already pays for
            prefix = "fmt_slo_burn_rate_"
            out["burn_rates"] = {
                k[len(prefix):]: float(v)
                for k, v in samples.items() if k.startswith(prefix)
            }
        return out


# -- the parent-side process handle -------------------------------------------


def _package_root() -> str:
    """Directory containing the ``flink_ml_tpu`` package — prepended to
    the child's ``PYTHONPATH`` so a repo-checkout parent (sys.path
    manipulation, no install) spawns importable children."""
    import flink_ml_tpu

    return os.path.dirname(os.path.dirname(
        os.path.abspath(flink_ml_tpu.__file__)))


def _child_env(env: Dict[str, str]) -> None:
    """What a child replica must inherit from the parent's RESOLVED state
    (the runtime may have picked values that are in neither os.environ nor
    the child's defaults):

    * the platform — ``JAX_PLATFORMS`` pinned to the parent's backend
      once the parent has one.  A chip belongs to one process: with the
      variable unset, a child whose parent holds the TPU falls through to
      XLA:CPU and the fleet silently serves from the host.  Pinned, that
      child dies at boot and :meth:`ReplicaProcess.spawn` raises with its
      log tail.  A parent that stayed off JAX holds no chip, so its
      children inherit the environment untouched (asking for the backend
      here would make the parent take the chip);
    * the compile cache — through JAX's own ``JAX_COMPILATION_CACHE_DIR``,
      so a kill -9 -> respawn replica replays the ladder's compiles;
    * the warm-artifact store (``FMT_WARM_DIR``)."""
    import jax

    from flink_ml_tpu.parallel.mesh import backend_initialized
    from flink_ml_tpu.serving import warmstart
    from flink_ml_tpu.utils import compile_cache

    if backend_initialized():
        env["JAX_PLATFORMS"] = jax.default_backend()
    d = compile_cache.cache_dir()
    if d:
        env["JAX_COMPILATION_CACHE_DIR"] = d
    store = warmstart.active()
    if store is not None:
        env.setdefault("FMT_WARM_DIR", store.root)


class ReplicaProcess:
    """One supervised replica child: spawn, handshake, watch, stop.

    ``spawn`` blocks until the child publishes BOTH addresses (data plane
    via ``--address-file``, telemetry via ``FMT_TELEMETRY_PORT_FILE``) or
    the boot deadline passes — an early exit surfaces the child's log
    tail, not a bare timeout.  The child's stdout/stderr land in
    ``<workdir>/replica.log``; its RunReports are isolated to the workdir
    so a fleet of children never races the parent's reports directory.
    """

    def __init__(self, proc: subprocess.Popen, workdir: str,
                 serve_address: str, telemetry_address: str,
                 model_path: str, version: str):
        self._proc = proc
        self.workdir = workdir
        self.serve_address = serve_address
        self.telemetry_address = telemetry_address
        self.model_path = model_path
        self.version = version

    @classmethod
    def spawn(cls, model_path: str, version: str, *,
              host: str = "127.0.0.1",
              extra_env: Optional[Dict[str, str]] = None,
              boot_timeout_s: Optional[float] = None) -> "ReplicaProcess":
        from flink_ml_tpu.fault.injection import maybe_fail
        from flink_ml_tpu.obs import telemetry
        from flink_ml_tpu.utils import knobs

        maybe_fail("router.spawn")
        if boot_timeout_s is None:
            boot_timeout_s = knobs.knob_float("FMT_ROUTER_SPAWN_TIMEOUT_S")
        workdir = tempfile.mkdtemp(prefix="fmt_replica_")
        serve_file = os.path.join(workdir, "serve.addr")
        telemetry_file = os.path.join(workdir, "telemetry.addr")
        env = dict(os.environ)
        env["FMT_TELEMETRY_PORT_FILE"] = telemetry_file
        # the child's registry must record (queue-depth balancing and
        # /metrics scrapes read it) and its reports must not race the
        # parent's committed reports dir
        env["FMT_OBS"] = "1"
        env["FMT_OBS_REPORTS"] = workdir
        # a parent-side chaos schedule is the PARENT's experiment: the
        # child starts fault-free unless the caller injects explicitly
        env.pop("FMT_FAULT_INJECT", None)
        if trace.enabled():
            # a traced fleet traces its replicas too, into the SAME
            # directory (per-pid filenames keep the writers apart) —
            # the runtime enable() may postdate the parent's env
            env["FMT_TRACE"] = "1"
            env["FMT_TRACE_DIR"] = trace.trace_dir()
            env.setdefault("FMT_TRACE_SAMPLE", str(trace.sample_rate()))
            env.setdefault("FMT_TRACE_TAIL", ",".join(trace.tail_modes()))
        _child_env(env)
        env["PYTHONPATH"] = _package_root() + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        if extra_env:
            env.update(extra_env)
        log_path = os.path.join(workdir, "replica.log")
        with open(log_path, "wb") as log:
            proc = subprocess.Popen(
                [sys.executable, "-m", "flink_ml_tpu.serving.replica",
                 "--model", str(model_path), "--version", str(version),
                 "--address-file", serve_file, "--host", host],
                stdout=log, stderr=subprocess.STDOUT, env=env,
            )
        deadline = time.monotonic() + boot_timeout_s
        while True:
            addresses = []
            for path in (serve_file, telemetry_file):
                try:
                    h, p = telemetry.read_port_file(path)
                    addresses.append(f"{h}:{p}")
                except (OSError, ValueError):
                    break
            if len(addresses) == 2:
                return cls(proc, workdir, addresses[0], addresses[1],
                           str(model_path), str(version))
            code = proc.poll()
            if code is not None:
                raise RuntimeError(
                    f"replica exited {code} during boot; log tail:\n"
                    + cls._tail(log_path)
                )
            if time.monotonic() > deadline:
                proc.kill()
                raise RuntimeError(
                    f"replica did not publish its endpoints within "
                    f"{boot_timeout_s:.0f}s; log tail:\n"
                    + cls._tail(log_path)
                )
            time.sleep(0.02)

    @staticmethod
    def _tail(log_path: str, n_bytes: int = 4000) -> str:
        try:
            with open(log_path, "rb") as f:
                f.seek(0, io.SEEK_END)
                f.seek(max(0, f.tell() - n_bytes))
                return f.read().decode(errors="replace")
        except OSError:
            return "<no log>"

    @property
    def pid(self) -> int:
        return self._proc.pid

    def alive(self) -> bool:
        return self._proc.poll() is None

    def poll_dead(self) -> Optional[int]:
        """The child's exit code, or None while it runs — the router's
        cheap per-poll liveness check (no syscall beyond waitpid)."""
        return self._proc.poll()

    def log_tail(self, n_bytes: int = 4000) -> str:
        return self._tail(os.path.join(self.workdir, "replica.log"),
                          n_bytes)

    def stop(self, grace_s: float = 10.0) -> None:
        """SIGTERM, wait up to ``grace_s`` (the child drains and exits
        0), then SIGKILL.  Idempotent on an already-dead child."""
        if self._proc.poll() is None:
            try:
                self._proc.send_signal(signal.SIGTERM)
            except ProcessLookupError:
                pass
            try:
                self._proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                self._proc.kill()
                self._proc.wait(timeout=5.0)

    def kill(self) -> None:
        """SIGKILL — the chaos lever (a crashed replica, simulated)."""
        if self._proc.poll() is None:
            try:
                self._proc.kill()
            except ProcessLookupError:
                pass
        self._proc.wait(timeout=5.0)


# -- the child entry point ----------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m flink_ml_tpu.serving.replica`` — one serving replica:
    load the model, bring up ModelServer + telemetry (ephemeral ports),
    publish both addresses, serve until SIGTERM/SIGINT, drain, exit 0."""
    import argparse

    parser = argparse.ArgumentParser(
        description="flink_ml_tpu serving replica (one ModelServer child)"
    )
    parser.add_argument("--model", required=True,
                        help="saved pipeline/stage directory to serve")
    parser.add_argument("--version", default="v1")
    parser.add_argument("--address-file", required=True,
                        help="file that receives the data-plane host:port")
    parser.add_argument("--host", default="127.0.0.1")
    args = parser.parse_args(argv)

    from flink_ml_tpu import obs
    from flink_ml_tpu.obs import telemetry
    from flink_ml_tpu.serving.server import ModelServer

    obs.enable()  # a replica's registry IS its control surface
    server = ModelServer(path=args.model, version=args.version,
                         telemetry_port=0)
    data = ReplicaDataServer(server, host=args.host).start()
    telemetry.write_port_file(args.address_file, args.host, data.port)

    stop_event = threading.Event()

    def _stop(signum, frame):  # noqa: ARG001 - signal contract
        stop_event.set()

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    print(f"replica pid={os.getpid()} serving {args.model!r} "
          f"version={args.version} data={data.address} "
          f"telemetry={server.telemetry_address}", flush=True)
    stop_event.wait()
    data.stop()
    server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
