"""ExecutorEndpoint: the process-boundary seam of the serving tier.

Everything through PR 10 ran queries in ONE process, and three
process-local assumptions leaked into the serving code: the memory
manager's kill/pressure hooks, the ONE SharedTaskPool, and the
process-global counters.  This module hides all of them behind one
interface so the fleet tier (serving/fleet.py) can schedule across
process boundaries the way the reference schedules across JVM executors
(PAPER.md: NativeRDD rides Spark's task retry; executor death is a
routine event the driver plans around):

- ``ExecutorEndpoint`` — what a FleetManager needs from one executor:
  dispatch / heartbeat / status / result / cancel / drain / close.
- ``LocalExecutor`` — today's in-process path: a QueryScheduler driven
  directly (the default; bit-identical to pre-fleet serving).
- ``ExecutorServer`` — the slim executor server a worker process runs:
  the same QueryScheduler exposed over the existing framed-TCP wire
  (shuffle_rss.server framing, the service/ protocol family).  Run one
  with ``python -m auron_tpu.serving.executor_endpoint``.
- ``ProcessExecutor`` — the driver-side client for one worker process
  (spawn + supervise, or connect to an already-running server).

Every client RPC is classified and retried through the ONE retry policy
(runtime/retry.py) with a named ``fault_point`` per RPC family
(``fleet.dispatch`` / ``fleet.heartbeat`` / ``fleet.status`` /
``fleet.result`` / ``fleet.cancel`` / ``fleet.drain`` /
``fleet.shutdown``) so the chaos harness can exercise the process
boundary like any other recovery site.  Transport failures (a dead or
restarting worker) are retryable-IO; an answered-but-failed RPC ferries
an ``EndpointError`` that is DETERMINISTIC by classification — the
executor processed the request, replaying the transport cannot change
the answer — and the ``auron_retry_exhausted`` marker crosses the
process boundary with it, so an outer retry site never multiplies a
budget the worker already spent.
"""

from __future__ import annotations

import io
import json
import logging
import os
import socket
import socketserver
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import pyarrow as pa

from auron_tpu.config import conf
from auron_tpu.faults import fault_point
from auron_tpu.runtime import lockcheck, wirecheck
from auron_tpu.runtime.retry import RetryPolicy, call_with_retry
from auron_tpu.shuffle_rss.server import recv_msg, send_msg

log = logging.getLogger("auron_tpu.serving.fleet")

# server-ingress frame cap (untrusted); client receive is unbounded —
# result tables can legitimately be large
MAX_REQUEST_PAYLOAD = 1 << 31


class EndpointError(RuntimeError):
    """Structured failure ferried from an executor over the wire.

    Deterministic by default (`auron_deterministic`): the RPC reached
    the executor and was answered, so the shared retry policy must not
    replay the transport.  `exhausted` mirrors the worker-side
    ``auron_retry_exhausted`` marker across the process boundary;
    `draining` marks the graceful-decommission refusal (the fleet
    reroutes instead of failing the query)."""

    def __init__(self, message: str, deterministic: bool = True,
                 exhausted: bool = False, draining: bool = False):
        super().__init__(message)
        self.auron_deterministic = bool(deterministic)
        self.draining = bool(draining)
        if exhausted:
            self.auron_retry_exhausted = True


def _table_ipc(table: pa.Table) -> bytes:
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as w:
        w.write_table(table)
    return sink.getvalue().to_pybytes()


def _table_from_ipc(data: bytes) -> pa.Table:
    with pa.ipc.open_stream(io.BytesIO(data)) as r:
        return r.read_all()


# worker-side process counters mirrored into heartbeat loads: the fleet
# aggregates them for /metrics (the driver cannot read another
# process's counter registry) and tests assert resume-vs-recompute
# through them
_REPORTED_COUNTERS = (
    "rss_stage_skips", "rss_map_tasks_skipped", "rss_map_tasks_run",
    "rss_fetch_regens", "rss_degrades", "tasks_retried",
    "trace_dropped_events", "shuffle_bytes_pushed",
    "shuffle_bytes_fetched",
)


def endpoint_load(scheduler, draining: bool = False) -> Dict[str, Any]:
    """One executor's live load telemetry: scheduler queue depth +
    running count, memory-pool usage, per-query memory peaks (the
    admission re-forecast feed) and the mirrored process counters."""
    from auron_tpu.memmgr import get_manager
    from auron_tpu.runtime import counters
    stats = scheduler.stats()
    mgr = get_manager()
    mem = mgr.stats()
    return {"running": stats.get("running", 0),
            "queued": stats.get("queued", 0),
            "states": stats.get("states", {}),
            "draining": draining,
            "mem": {"used": mem.get("total_used", 0),
                    "budget": mem.get("budget", 0)},
            "query_mem": {qid: int(ent.get("peak") or
                                   ent.get("used") or 0)
                          for qid, ent in mgr.query_ledger().items()},
            "counters": {k: counters.get(k)
                         for k in _REPORTED_COUNTERS}}


def _serial_overlay(conf_map: Dict[str, Any],
                    serial: bool) -> Dict[str, Any]:
    """The degrade-to-serial conf the admission controller decided,
    applied as part of the per-query overlay (the executor-side
    scheduler runs with pass-through admission, so the fleet's decision
    has to travel with the dispatch)."""
    if not serial:
        return dict(conf_map)
    out = dict(conf_map)
    out["auron.task.parallelism"] = 1
    out["auron.spmd.singleDevice.enable"] = False
    return out


class ExecutorEndpoint:
    """One executor as the fleet sees it.  Implementations hide where
    the work runs; the fleet only ever talks in query ids."""

    executor_id: str
    # True when harvest() actually crosses a process boundary (the
    # fleet only stitches/records driver-side QueryRecords for remote
    # executors — an in-process LocalExecutor already records into the
    # driver's own history ring)
    supports_harvest = False

    def dispatch(self, query_id: str, plan, conf_map: Dict[str, Any],
                 priority: Optional[int], serial: bool = False) -> None:
        """Hand the executor a submission under `query_id` (unique per
        executor).  Raises on refusal (EndpointError) or transport
        failure after retries."""
        raise NotImplementedError

    def heartbeat(self, ids: Optional[List[str]] = None
                  ) -> Dict[str, Any]:
        """Liveness probe; returns ``{"load": {...}, "queries": {id:
        status-dict-or-None for each requested id}}``."""
        raise NotImplementedError

    def status(self, query_id: str) -> Optional[Dict[str, Any]]:
        raise NotImplementedError

    def result(self, query_id: str) -> pa.Table:
        """The result table of a SUCCEEDED query (raises otherwise)."""
        raise NotImplementedError

    def cancel(self, query_id: str) -> bool:
        raise NotImplementedError

    def harvest(self, ids: List[str]) -> Dict[str, Any]:
        """Trace/record harvest riding the heartbeat cadence: for each
        requested query id, the executor's span increments (a running
        traced query is DRAINED — runtime/tracing.harvest_query) or its
        finished QueryRecord summary with residual spans.  Default: no
        cross-process state to ship ({})."""
        return {}

    def drain(self) -> List[str]:
        """Stop accepting dispatches and hand back the queued (never
        started) query ids so the caller can reroute them; running
        queries keep running."""
        raise NotImplementedError

    def kill(self) -> None:
        """Fence a dead-declared executor (best effort, idempotent):
        its in-flight queries are being requeued elsewhere, so a
        half-alive incarnation must not keep executing them."""

    def close(self) -> None:
        """Graceful teardown (shutdown RPC / scheduler shutdown)."""
        raise NotImplementedError

    def describe(self) -> Dict[str, Any]:
        return {"executor_id": self.executor_id,
                "kind": type(self).__name__}


# ---------------------------------------------------------------------------
# in-process endpoint (the default path — bit-identical to pre-fleet)
# ---------------------------------------------------------------------------

class LocalExecutor(ExecutorEndpoint):
    """Today's in-process execution behind the endpoint interface: a
    QueryScheduler with pass-through admission (the fleet's controller
    is the front door).  No sockets, no subprocesses — a fleet of one
    LocalExecutor is the single-process serving tier with a thin
    routing layer on top."""

    def __init__(self, executor_id: str = "local-0",
                 session_factory=None, scheduler=None):
        from auron_tpu.serving.admission import PassThroughAdmission
        from auron_tpu.serving.scheduler import QueryScheduler
        self.executor_id = executor_id
        self.scheduler = scheduler or QueryScheduler(
            session_factory=session_factory,
            admission=PassThroughAdmission())

    def dispatch(self, query_id: str, plan, conf_map: Dict[str, Any],
                 priority: Optional[int], serial: bool = False) -> None:
        from auron_tpu.serving.scheduler import SubmissionRejected
        try:
            self.scheduler.submit(plan,
                                  conf=_serial_overlay(conf_map, serial),
                                  priority=priority, query_id=query_id)
        except SubmissionRejected as e:
            raise EndpointError(str(e)) from e

    def heartbeat(self, ids: Optional[List[str]] = None
                  ) -> Dict[str, Any]:
        return {"executor_id": self.executor_id, "pid": os.getpid(),
                "now": time.time(),
                "load": endpoint_load(self.scheduler),
                "queries": {i: self.scheduler.status(i)
                            for i in (ids or [])}}

    def status(self, query_id: str) -> Optional[Dict[str, Any]]:
        return self.scheduler.status(query_id)

    def result(self, query_id: str) -> pa.Table:
        table = self.scheduler.result(query_id)
        if table is None:
            raise EndpointError(f"no result for query {query_id!r}")
        return table

    def cancel(self, query_id: str) -> bool:
        return self.scheduler.cancel(query_id)

    def drain(self) -> List[str]:
        moved = []
        for qid in self.scheduler.queued_ids():
            if self.scheduler.cancel(qid):
                moved.append(qid)
        return moved

    def close(self) -> None:
        self.scheduler.shutdown()


# ---------------------------------------------------------------------------
# the slim executor server (worker-process side)
# ---------------------------------------------------------------------------

class _TCPServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class _ExecHandler(socketserver.BaseRequestHandler):
    def handle(self) -> None:
        server: "ExecutorServer" = \
            self.server.exec_server  # type: ignore[attr-defined]
        sock = self.request
        from auron_tpu.shuffle_rss.server import read_timeout
        sock.settimeout(read_timeout())
        while True:
            try:
                header, payload = recv_msg(sock, MAX_REQUEST_PAYLOAD)
            except (ConnectionError, OSError, ValueError):
                return
            # version handshake (fix-forward, always on): refuse a
            # newer-major peer with a structured frame, then close
            refusal = wirecheck.peer_refusal(header)
            if refusal is not None:
                try:
                    send_msg(sock, wirecheck.refusal_frame(
                        "executor", refusal,
                        peer=f"{self.client_address[0]}:"
                             f"{self.client_address[1]}"))
                except (BrokenPipeError, ConnectionError, OSError):
                    pass
                return
            # shared-secret wire auth (since 1.1, always on like the
            # handshake): missing/wrong token -> structured
            # deterministic refusal, then close
            denied = wirecheck.auth_refusal(header)
            if denied is not None:
                try:
                    send_msg(sock, wirecheck.refusal_frame(
                        "executor", denied,
                        peer=f"{self.client_address[0]}:"
                             f"{self.client_address[1]}"))
                except (BrokenPipeError, ConnectionError, OSError):
                    pass
                return
            # frame conformance (enabled-only): answered in-band, the
            # connection survives
            problem = wirecheck.request_problem("executor", header)
            if problem is not None:
                try:
                    send_msg(sock, {"ok": False, "deterministic": True,
                                    "error": problem})
                except (BrokenPipeError, ConnectionError, OSError):
                    return
                continue
            wirecheck.note_frame("executor", header.get("cmd"))
            try:
                if not self._dispatch(server, sock, header, payload):
                    return
            except (BrokenPipeError, ConnectionError):
                return
            except BaseException as e:  # noqa: BLE001 - ferried in-band
                # an answered failure is DETERMINISTIC for the client's
                # retry policy; the exhausted marker crosses the wire
                try:
                    send_msg(sock, {
                        "ok": False,
                        "error": f"{type(e).__name__}: {e}",
                        "deterministic": not getattr(
                            e, "auron_retryable", False),
                        "exhausted": bool(getattr(
                            e, "auron_retry_exhausted", False))})
                except (BrokenPipeError, ConnectionError, OSError):
                    return

    def _dispatch(self, server: "ExecutorServer", sock, header: dict,
                  payload: bytes) -> bool:
        cmd = header.get("cmd")
        sched = server.scheduler
        if cmd in ("ping", "hello"):
            send_msg(sock, {"ok": True,
                            "executor_id": server.executor_id,
                            "pid": os.getpid(),
                            "proto_version": wirecheck.proto_version()})
            return True
        if cmd == "heartbeat":
            ids = header.get("ids") or []
            send_msg(sock, {"ok": True,
                            "executor_id": server.executor_id,
                            "pid": os.getpid(),
                            "now": time.time(),
                            "load": server.load(),
                            "queries": {i: sched.status(i)
                                        for i in ids}})
            return True
        if cmd == "harvest":
            from auron_tpu.runtime import tracing
            traces = {}
            for qid in header.get("ids") or []:
                doc = tracing.harvest_query(str(qid))
                if doc is not None:
                    traces[qid] = doc
            # span batches ride the PAYLOAD: a traced query can carry
            # far more span JSON than the (untrusted-ingress) 1 MiB
            # header cap allows
            body = json.dumps(traces).encode()
            send_msg(sock, {"ok": True, "pid": os.getpid(),
                            "now": time.time(), "len": len(body)}, body)
            return True
        if cmd == "dispatch":
            if server.draining:
                send_msg(sock, {"ok": False, "draining": True,
                                "deterministic": True,
                                "error": "executor draining"})
                return True
            from auron_tpu.frontend.foreign import ForeignNode
            from auron_tpu.serving.scheduler import SubmissionRejected
            plan = ForeignNode.from_dict(json.loads(payload))
            try:
                sched.submit(plan, conf=header.get("conf") or {},
                             priority=header.get("priority"),
                             query_id=str(header.get("query_id")))
            except SubmissionRejected as e:
                send_msg(sock, {"ok": False, "deterministic": True,
                                "error": str(e)})
                return True
            send_msg(sock, {"ok": True})
            return True
        if cmd == "status":
            send_msg(sock, {"ok": True,
                            "status": sched.status(
                                str(header.get("query_id")))})
            return True
        if cmd == "result":
            qid = str(header.get("query_id"))
            sub = sched.get(qid)
            if sub is None or sub.result is None:
                state = sub.state if sub is not None else "unknown"
                send_msg(sock, {"ok": False, "deterministic": True,
                                "error": f"query {qid!r} has no result "
                                         f"(state {state})"})
                return True
            data = _table_ipc(sub.result)
            send_msg(sock, {"ok": True, "len": len(data),
                            "rows": sub.result.num_rows}, data)
            return True
        if cmd == "cancel":
            send_msg(sock, {"ok": True,
                            "cancelled": sched.cancel(
                                str(header.get("query_id")))})
            return True
        if cmd == "drain":
            server.set_draining()
            moved = []
            for qid in sched.queued_ids():
                if sched.cancel(qid):
                    moved.append(qid)
            send_msg(sock, {"ok": True, "moved": moved})
            return True
        if cmd == "shutdown":
            send_msg(sock, {"ok": True})
            threading.Thread(target=server.stop, daemon=True).start()
            return False
        send_msg(sock, {"ok": False, "deterministic": True,
                        "error": f"unknown cmd {cmd!r}"})
        return True


class ExecutorServer:
    """One worker process's serve loop: a QueryScheduler (pass-through
    admission — the fleet's controller is the front door) behind the
    framed-TCP wire.  Binds loopback by default; non-loopback
    deployments set `auron.net.auth.secret` so every frame carries a
    shared-secret token the server verifies."""

    def __init__(self, scheduler=None, session_factory=None,
                 executor_id: str = "exec-0",
                 host: str = "127.0.0.1", port: int = 0):
        from auron_tpu.serving.admission import PassThroughAdmission
        from auron_tpu.serving.scheduler import QueryScheduler
        self.executor_id = executor_id
        self.scheduler = scheduler or QueryScheduler(
            session_factory=session_factory,
            admission=PassThroughAdmission())
        self._draining = False
        self._lock = lockcheck.Lock("fleet.executor.server")
        self._tcp = _TCPServer((host, port), _ExecHandler)
        self._tcp.exec_server = self  # type: ignore[attr-defined]
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> Tuple[str, int]:
        return self._tcp.server_address[:2]

    @property
    def draining(self) -> bool:
        with self._lock:
            return self._draining

    def set_draining(self) -> None:
        with self._lock:
            self._draining = True

    def load(self) -> Dict[str, Any]:
        return endpoint_load(self.scheduler, draining=self.draining)

    def start(self) -> "ExecutorServer":
        self._thread = threading.Thread(
            target=self._tcp.serve_forever, daemon=True,
            name=f"auron-fleet-server-{self.executor_id}")
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self._tcp.serve_forever()

    def stop(self) -> None:
        self.scheduler.shutdown()
        self._tcp.shutdown()
        self._tcp.server_close()


# ---------------------------------------------------------------------------
# driver-side client for one worker process
# ---------------------------------------------------------------------------

class ProcessExecutor(ExecutorEndpoint):
    """Client for one ExecutorServer, optionally owning the worker
    process it spawned.  Connections are per-RPC (no shared socket
    state to corrupt when the worker dies mid-call), and every RPC
    rides the shared retry policy behind its named fault point."""

    supports_harvest = True

    def __init__(self, executor_id: str, host: str, port: int,
                 proc: Optional[subprocess.Popen] = None,
                 log_path: Optional[str] = None):
        self.executor_id = executor_id
        self.host, self.port = host, int(port)
        self.proc = proc
        self.log_path = log_path
        self._log_file = None        # spawn() attaches the stderr sink

    # -- process supervision ------------------------------------------------

    @classmethod
    def spawn(cls, executor_id: str,
              conf_map: Optional[Dict[str, Any]] = None,
              budget_bytes: int = 0,
              log_dir: Optional[str] = None,
              launcher=None) -> "ProcessExecutor":
        """Launch a worker process running `python -m
        auron_tpu.serving.executor_endpoint` and wait for its listening
        line (`auron.fleet.boot.timeout.seconds`).  `launcher` (a
        serving.fleet.WorkerLauncher) may wrap the argv — the
        ssh/k8s-shaped remote seam; None spawns locally as before."""
        from auron_tpu import config
        cmd = [sys.executable, "-m",
               "auron_tpu.serving.executor_endpoint",
               "--executor-id", executor_id, "--port", "0"]
        if conf_map:
            # redacted keys (the wire secret) never ride argv — they
            # are visible in /proc cmdline; workers read their own env
            cmd += ["--conf", json.dumps(
                config.redact_overlay(conf_map))]
        if budget_bytes:
            cmd += ["--budget", str(int(budget_bytes))]
        if launcher is not None:
            cmd = launcher.wrap(cmd)
        if log_dir is None:
            log_dir = tempfile.mkdtemp(prefix="auron-fleet-")
        log_path = os.path.join(log_dir, f"{executor_id}.log")
        log_file = open(log_path, "wb")  # noqa: SIM115 - worker lifetime
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=log_file, text=True,
                                env=dict(os.environ))
        timeout = float(conf.get("auron.fleet.boot.timeout.seconds"))
        info = cls._await_listening(proc, timeout, executor_id, log_path)
        ep = cls(executor_id, info["host"], info["port"], proc=proc,
                 log_path=log_path)
        ep._log_file = log_file
        # keep draining stdout so the worker can never block on a full
        # pipe (it prints almost nothing after the listening line)
        threading.Thread(target=cls._drain_stdout, args=(proc,),
                         daemon=True,
                         name=f"auron-fleet-stdout-{executor_id}").start()
        return ep

    @staticmethod
    def _await_listening(proc: subprocess.Popen, timeout: float,
                         executor_id: str, log_path: str) -> dict:
        box: Dict[str, Any] = {}

        def _read():
            for line in proc.stdout:   # scan past any stray output
                line = line.strip()
                if not line.startswith("{"):
                    continue
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
                if doc.get("event") == "listening":
                    box["info"] = doc
                    return

        t = threading.Thread(target=_read, daemon=True)
        t.start()
        t.join(timeout)
        if "info" not in box:
            proc.kill()
            tail = ""
            try:
                with open(log_path, "rb") as f:
                    tail = f.read()[-2000:].decode("utf-8", "replace")
            except OSError:
                pass
            raise RuntimeError(
                f"executor {executor_id!r} did not report listening "
                f"within {timeout:g}s; log tail:\n{tail}")
        return box["info"]

    @staticmethod
    def _drain_stdout(proc: subprocess.Popen) -> None:
        try:
            for _ in proc.stdout:
                pass
        except (OSError, ValueError):
            pass

    @property
    def pid(self) -> Optional[int]:
        return self.proc.pid if self.proc is not None else None

    # -- the RPC spine ------------------------------------------------------

    def _timeout(self) -> Optional[float]:
        t = float(conf.get("auron.net.timeout.seconds"))
        return t if t > 0 else None

    def _rpc(self, site: str, header: dict, payload: bytes = b"",
             max_attempts: Optional[int] = None) -> Tuple[dict, bytes]:
        """One request/response over a fresh connection, retried
        through the shared policy.  Transport errors are retryable-IO;
        an answered failure raises EndpointError (deterministic, with
        the worker's exhausted marker mirrored)."""
        wirecheck.attach_token(header)
        wirecheck.check_request("executor", header)

        def _once():
            fault_point(f"fleet.{site}")
            s = socket.create_connection((self.host, self.port),
                                         timeout=self._timeout())
            try:
                send_msg(s, header, payload)
                resp, data = recv_msg(s)
            finally:
                try:
                    s.close()
                except OSError:
                    pass
            if not resp.get("ok", False):
                raise EndpointError(
                    resp.get("error", "rpc failed"),
                    deterministic=resp.get("deterministic", True),
                    exhausted=resp.get("exhausted", False),
                    draining=resp.get("draining", False))
            return resp, data

        resp, data = call_with_retry(
            _once, policy=RetryPolicy.from_conf(max_attempts),
            label=f"fleet {site} -> {self.executor_id}")
        wirecheck.check_response("executor", str(header.get("cmd")),
                                 resp)
        return resp, data

    # -- endpoint surface ---------------------------------------------------

    def hello(self) -> dict:
        """First contact: assert this client's protocol version and
        check the server's advertisement — a newer-major server is
        refused with a structured EndpointError (flight-recorder
        `wire.refusal` event), never a garbled decode later."""
        resp, _ = self._rpc("status", {
            "cmd": "hello", "proto": wirecheck.proto_version()})
        refusal = wirecheck.advertised_refusal(resp)
        if refusal is not None:
            from auron_tpu.runtime import counters, events
            counters.bump("wire_rejects")
            events.emit("wire.refusal", refusal, wire="executor",
                        peer=f"{self.host}:{self.port}",
                        proto_version=wirecheck.proto_version())
            raise EndpointError(refusal)
        return resp

    def dispatch(self, query_id: str, plan, conf_map: Dict[str, Any],
                 priority: Optional[int], serial: bool = False) -> None:
        data = json.dumps(plan.to_dict()).encode()
        self._rpc("dispatch",
                  {"cmd": "dispatch", "query_id": query_id,
                   "conf": _serial_overlay(conf_map, serial),
                   "priority": priority, "len": len(data)}, data)

    def heartbeat(self, ids: Optional[List[str]] = None
                  ) -> Dict[str, Any]:
        resp, _ = self._rpc("heartbeat",
                            {"cmd": "heartbeat", "ids": list(ids or [])})
        return resp

    def status(self, query_id: str) -> Optional[Dict[str, Any]]:
        resp, _ = self._rpc("status",
                            {"cmd": "status", "query_id": query_id})
        return resp.get("status")

    def result(self, query_id: str) -> pa.Table:
        _, data = self._rpc("result",
                            {"cmd": "result", "query_id": query_id})
        return _table_from_ipc(data)

    def cancel(self, query_id: str) -> bool:
        resp, _ = self._rpc("cancel",
                            {"cmd": "cancel", "query_id": query_id})
        return bool(resp.get("cancelled"))

    def harvest(self, ids: List[str]) -> Dict[str, Any]:
        _, data = self._rpc("harvest",
                            {"cmd": "harvest", "ids": list(ids)})
        return json.loads(data) if data else {}

    def drain(self) -> List[str]:
        resp, _ = self._rpc("drain", {"cmd": "drain"})
        return list(resp.get("moved") or [])

    def kill(self) -> None:
        """SIGKILL the worker (fence against double execution after a
        death declaration); no-op for an unowned connection."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
        self._reap()

    def _reap(self) -> None:
        if self.proc is not None:
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        if self._log_file is not None:
            try:
                self._log_file.close()
            except OSError:
                pass
            self._log_file = None

    def close(self) -> None:
        """Graceful teardown: shutdown RPC (best effort, one attempt),
        then terminate/kill the owned process."""
        try:
            self._rpc("shutdown", {"cmd": "shutdown"}, max_attempts=1)
        except BaseException:  # noqa: BLE001 - already dying is fine
            pass
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        self._reap()

    def describe(self) -> Dict[str, Any]:
        return {"executor_id": self.executor_id,
                "kind": type(self).__name__,
                "host": self.host, "port": self.port, "pid": self.pid,
                "log": self.log_path}


# ---------------------------------------------------------------------------
# worker entry point
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    """`python -m auron_tpu.serving.executor_endpoint` — run one
    executor server (the FleetManager's spawn target)."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m auron_tpu.serving.executor_endpoint",
        description="Auron TPU fleet executor server")
    ap.add_argument("--host", default=None,
                    help="bind address (default: auron.net.bind.host)")
    ap.add_argument("--advertise-host", default=None,
                    help="host the driver should dial (default: "
                         "auron.net.advertise.host, else the bind "
                         "host; wildcard binds advertise loopback)")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--executor-id", default="exec-0")
    ap.add_argument("--conf", default="",
                    help="JSON map of process-wide conf overrides")
    ap.add_argument("--budget", type=int, default=0,
                    help="MemManager budget bytes (the fleet's "
                         "per-worker slice of the federated budget)")
    args = ap.parse_args(argv)

    if args.conf:
        for key, value in json.loads(args.conf).items():
            conf.set(key, value)
    # worker records ship to the driver over harvest and the DRIVER
    # owns the durable stats store — disarm it here so a conf overlay
    # leaking auron.stats.store.dir cannot double-fold every query
    from auron_tpu.runtime import statshist
    statshist.mark_worker()
    if args.budget:
        from auron_tpu.memmgr.manager import reset_manager
        reset_manager(int(args.budget))
    from auron_tpu import config
    bind_host = args.host if args.host is not None \
        else config.net_bind_host()
    srv = ExecutorServer(executor_id=args.executor_id,
                         host=bind_host, port=args.port)
    host, port = srv.address
    adv = args.advertise_host if args.advertise_host is not None \
        else config.net_advertise_host(host)
    print(json.dumps({"event": "listening", "host": adv, "port": port,
                      "executor_id": args.executor_id,
                      "pid": os.getpid(),
                      "proto_version": wirecheck.proto_version()}),
          flush=True)
    srv.serve_forever()
    return 0


if __name__ == "__main__":
    sys.exit(main())
