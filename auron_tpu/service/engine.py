"""Engine boundary service: serialized plans in, Arrow batches out.

The out-of-process counterpart of the reference's JNI bridge — the four
native methods (callNative / nextBatch / finalizeNative / onExit,
JniBridge.java:49-55, native-engine/auron/src/exec.rs:42-144) and the
resource map upcalls (JniBridge.putResource/getResource) become commands
on one framed TCP channel, so ANY host process (JVM, C++, Python) can
drive the engine the way AuronCallNativeWrapper does in-process.

Wire protocol (shared framing with shuffle_rss.server: 4-byte big-endian
header length, JSON header, raw payload):

  {"cmd": "ping"}                                   -> {"ok": true}
  {"cmd": "put_resource", "key": K,
   "kind": "arrow_ipc"|"bytes", "len": N} + payload -> {"ok": true}
  {"cmd": "delete_resource", "key": K}              -> {"ok": true}
  {"cmd": "execute", "len": N} + TaskDefinition     -> stream of
       {"type": "batch", "len": N} + one-batch Arrow IPC stream
       ... then {"type": "done", "metrics": {...}}
       or       {"type": "error", "message": ..., "traceback": ...}
  {"cmd": "shutdown"}                               -> {"ok": true}

Errors during execution are ferried in-band and the connection stays
usable — the setError + rethrow-on-next-loadNextBatch contract
(rt.rs:207-238, AuronCallNativeWrapper.java:158-168).
"""

from __future__ import annotations

import io
import json
import logging
import socket
import socketserver
import threading
import traceback
from typing import Any, Iterator, List, Optional, Tuple

import pyarrow as pa

from auron_tpu.config import conf
from auron_tpu.faults import fault_point
from auron_tpu.runtime import wirecheck
from auron_tpu.runtime.retry import RetryPolicy, call_with_retry
from auron_tpu.shuffle_rss.server import read_timeout, recv_msg, send_msg

log = logging.getLogger("auron_tpu.service")

# server-ingress frame cap (untrusted); client receive is unbounded —
# result batches can legitimately be large
MAX_REQUEST_PAYLOAD = 1 << 31


def _batch_ipc(rb: pa.RecordBatch) -> bytes:
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, rb.schema) as w:
        w.write_batch(rb)
    return sink.getvalue().to_pybytes()


def _batches_from_ipc(data: bytes) -> List[pa.RecordBatch]:
    with pa.ipc.open_stream(io.BytesIO(data)) as r:
        return list(r)


def _batches_to_ipc(source) -> bytes:
    """Serialize a Table / iterable of RecordBatches / zero-arg callable
    returning either, as one Arrow IPC stream."""
    if callable(source):
        source = source()
    if isinstance(source, pa.Table):
        source = source.to_batches()
    batches = list(source)
    sink = pa.BufferOutputStream()
    schema = batches[0].schema if batches else pa.schema([])
    with pa.ipc.new_stream(sink, schema) as w:
        for rb in batches:
            w.write_batch(rb)
    return sink.getvalue().to_pybytes()


class _Handler(socketserver.BaseRequestHandler):
    def handle(self) -> None:
        server: "EngineServer" = self.server.engine  # type: ignore[attr-defined]
        sock = self.request
        # read timeout (auron.service.read.timeout.seconds): a half-dead
        # client that stops sending mid-conversation is disconnected
        # instead of pinning this handler thread forever
        sock.settimeout(read_timeout())
        while True:
            try:
                header, payload = recv_msg(sock, MAX_REQUEST_PAYLOAD)
                # injected dispatch fault: drops the connection so the
                # client's retry policy (reconnect + replay) is exercised
                fault_point("service.dispatch")
            except (ConnectionError, OSError):
                return
            except ValueError:
                return  # oversized/garbled frame: drop the connection
            # version handshake (fix-forward, always on): refuse a
            # newer-major peer with a structured frame, then close
            refusal = wirecheck.peer_refusal(header)
            if refusal is not None:
                try:
                    send_msg(sock, wirecheck.refusal_frame(
                        "engine", refusal,
                        peer=f"{self.client_address[0]}:"
                             f"{self.client_address[1]}"))
                except (BrokenPipeError, ConnectionError, OSError):
                    pass
                return
            # shared-secret auth (always on when the secret is set):
            # a bad/missing token gets a structured refusal, then the
            # connection closes — deterministic, so retries never spin
            denied = wirecheck.auth_refusal(header)
            if denied is not None:
                try:
                    send_msg(sock, wirecheck.refusal_frame(
                        "engine", denied,
                        peer=f"{self.client_address[0]}:"
                             f"{self.client_address[1]}"))
                except (BrokenPipeError, ConnectionError, OSError):
                    pass
                return
            # frame conformance (enabled-only): answered in-band, the
            # connection (and every resource registered on it) survives
            problem = wirecheck.request_problem("engine", header)
            if problem is not None:
                try:
                    send_msg(sock, {"ok": False,
                                    "error": problem})
                except (BrokenPipeError, ConnectionError, OSError):
                    return
                continue
            wirecheck.note_frame("engine", header.get("cmd"))
            try:
                if not self._dispatch(server, sock, header, payload):
                    return
            except (BrokenPipeError, ConnectionError):
                return
            except Exception as e:  # noqa: BLE001 - keep connection
                # malformed payloads (corrupt IPC, bad keys) answer
                # in-band instead of tearing the connection down with
                # every resource registered on it
                try:
                    send_msg(sock, {"ok": False,
                                    "error": f"{type(e).__name__}: {e}"})
                except (BrokenPipeError, ConnectionError, OSError):
                    return

    def _dispatch(self, server: "EngineServer", sock, header: dict,
                  payload: bytes) -> bool:
        cmd = header.get("cmd")
        if cmd == "ping":
            send_msg(sock, {"ok": True})
            return True
        if cmd == "put_resource":
            key = str(header.get("key"))
            kind = header.get("kind", "bytes")
            if kind == "arrow_ipc":
                server.resources.put(key, _batches_from_ipc(payload))
            else:
                server.resources.put(key, payload)
            send_msg(sock, {"ok": True})
            return True
        if cmd == "delete_resource":
            server.resources.pop(str(header.get("key")))
            send_msg(sock, {"ok": True})
            return True
        if cmd == "execute":
            self._execute(server, sock, payload)
            return True
        if cmd == "shutdown":
            send_msg(sock, {"ok": True})
            threading.Thread(target=server.stop, daemon=True).start()
            return False
        send_msg(sock, {"ok": False, "error": f"unknown cmd {cmd!r}"})
        return True

    def _execute(self, server: "EngineServer", sock,
                 task_bytes: bytes) -> None:
        from auron_tpu.ir import plan as P
        from auron_tpu.ir import serde as ir_serde
        from auron_tpu.runtime.executor import NativeExecutionRuntime
        from auron_tpu.runtime import task_logging
        try:
            td = ir_serde.deserialize(task_bytes)
            if not isinstance(td, P.TaskDefinition):
                raise TypeError(
                    f"expected TaskDefinition, got {type(td).__name__}")
            resources = _UpcallRegistry(server.resources, sock)
            rt = NativeExecutionRuntime(td, resources)
            task_logging.install()
            with task_logging.task_scope(td.stage_id, td.partition_id):
                for b in rt.batches():
                    rb = b.to_arrow()
                    if rb.num_rows == 0:
                        continue
                    data = _batch_ipc(rb)
                    send_msg(sock, {"type": "batch", "len": len(data)}, data)
            send_msg(sock, {"type": "done",
                            "metrics": rt.finalize().to_dict()})
        except (BrokenPipeError, ConnectionError):
            raise
        except BaseException as e:  # noqa: BLE001 - ferried to the peer
            send_msg(sock, {"type": "error", "message": str(e),
                            "traceback": traceback.format_exc()})


class _UpcallRegistry:
    """Resource registry with a mid-execution UPCALL to the driving host:
    a miss sends {"type": "need_resource"} on the execute channel and
    blocks for the host's inline reply — the out-of-process counterpart
    of the JavaClasses getResource upcall (jni_bridge.rs:419-470,
    ConvertToNativeBase.scala putResource/FFIReader flow)."""

    def __init__(self, base, sock):
        self._base = base
        self._sock = sock

    def put(self, key, value):
        self._base.put(key, value)

    def pop(self, key, default=None):
        return self._base.pop(key, default)

    def contains(self, key):
        return self._base.contains(key) or self._fetch(key)

    def get(self, key):
        if not self._base.contains(key):
            if not self._fetch(key):
                raise KeyError(key)
        return self._base.get(key)

    def _fetch(self, key) -> bool:
        send_msg(self._sock, {"type": "need_resource", "key": str(key)})
        header, payload = recv_msg(self._sock, MAX_REQUEST_PAYLOAD)
        if header.get("cmd") != "resource_data":
            raise RuntimeError(
                f"expected resource_data reply, got {header!r}")
        kind = header.get("kind")
        if kind == "missing":
            return False
        if kind == "arrow_ipc":
            self._base.put(str(key), _batches_from_ipc(payload))
        else:
            self._base.put(str(key), payload)
        return True


class _TCPServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class EngineServer:
    """Serve loop owning one resource registry (the JVM resource map
    analogue); binds loopback by default.  The channel is unauthenticated
    like the in-process JNI surface it replaces unless
    `auron.net.auth.secret` is set, in which case every frame must carry
    the matching token."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 resources=None):
        from auron_tpu.runtime.resources import ResourceRegistry
        self.resources = resources if resources is not None \
            else ResourceRegistry()
        self._server = _TCPServer((host, port), _Handler)
        self._server.engine = self  # type: ignore[attr-defined]
        self._thread: Optional[threading.Thread] = None

    @property
    def address(self) -> Tuple[str, int]:
        return self._server.server_address[:2]

    def start(self) -> "EngineServer":
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name="auron-engine-service")
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self._server.serve_forever()

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()


def serve(host: Optional[str] = None, port: int = 0,
          advertise_host: Optional[str] = None) -> None:
    """Blocking entry point (`python -m auron_tpu.service.engine`)."""
    from auron_tpu import config
    if host is None:
        host = config.net_bind_host()
    s = EngineServer(host, port)
    adv = advertise_host if advertise_host is not None \
        else config.net_advertise_host(host)
    print(json.dumps({"event": "listening", "host": adv,
                      "port": s.address[1],
                      "proto_version": wirecheck.proto_version()}),
          flush=True)
    s.serve_forever()


class RemoteExecutionError(RuntimeError):
    """The engine ANSWERED with a ferried failure.  Deterministic for
    the shared retry policy by declaration (not just by the RuntimeError
    default): the request reached the server, so a transport replay
    reproduces the same answer."""

    auron_deterministic = True

    def __init__(self, message: str, remote_traceback: str = ""):
        super().__init__(message)
        self.remote_traceback = remote_traceback


class EngineClient:
    """Foreign-host driver: the AuronCallNativeWrapper counterpart.

    Control-plane calls (ping/put/delete) ride the shared retry policy
    with transparent reconnect — they are idempotent (puts overwrite,
    deletes tolerate absence, and the server's resource registry
    outlives connections).  `execute_stream` replays only while no batch
    has been yielded yet: a mid-stream failure cannot be spliced, so it
    ferries."""

    def __init__(self, host: str, port: int,
                 timeout: Optional[float] = None):
        self.host, self.port = host, port
        if timeout is None:
            t = float(conf.get("auron.net.timeout.seconds"))
            timeout = t if t > 0 else None
        self._timeout = timeout
        self._provided: dict = {}
        self._sock: Optional[socket.socket] = None
        self._ensure_sock()

    def _ensure_sock(self) -> socket.socket:
        if self._sock is None:
            self._sock = socket.create_connection(
                (self.host, self.port), timeout=self._timeout)
        return self._sock

    def provide(self, key: str, source) -> None:
        """Register a resource served ON DEMAND through the in-band
        upcall (the ArrowFFIExporter/putResource flow): `source` is a
        Table, an iterable of RecordBatches, or a zero-arg callable
        returning either — materialized only if the engine asks."""
        self._provided[str(key)] = source

    def close(self) -> None:
        s, self._sock = self._sock, None
        if s is not None:
            try:
                s.close()
            except OSError:
                pass

    def __enter__(self) -> "EngineClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _call(self, header: dict, payload: bytes = b"") -> dict:
        wirecheck.attach_token(header)
        wirecheck.check_request("engine", header)

        def _once():
            fault_point("service.call")
            s = self._ensure_sock()
            try:
                send_msg(s, header, payload)
                resp, _ = recv_msg(s)
            except (OSError, EOFError):
                self.close()   # next attempt reconnects
                raise
            return resp

        resp = call_with_retry(
            _once, policy=RetryPolicy.from_conf(),
            label=f"engine {header.get('cmd')} to {self.host}:{self.port}")
        wirecheck.check_response("engine", str(header.get("cmd")), resp)
        if not resp.get("ok"):
            raise RemoteExecutionError(resp.get("error", "request failed"))
        return resp

    def ping(self) -> bool:
        return bool(self._call({"cmd": "ping"}).get("ok"))

    def put_arrow(self, key: str, batches) -> None:
        """Register Arrow data under `key` (putResource analogue);
        accepts a Table or an iterable of RecordBatches."""
        data = _batches_to_ipc(batches)
        self._call({"cmd": "put_resource", "key": key, "kind": "arrow_ipc",
                    "len": len(data)}, data)

    def put_bytes(self, key: str, data: bytes) -> None:
        self._call({"cmd": "put_resource", "key": key, "kind": "bytes",
                    "len": len(data)}, data)

    def delete_resource(self, key: str) -> None:
        self._call({"cmd": "delete_resource", "key": key})

    def execute_stream(self, task: Any) -> Iterator[pa.RecordBatch]:
        """Ship a TaskDefinition (object or serialized bytes), stream the
        result batches; raises RemoteExecutionError on a ferried failure.
        Metrics from the final frame land in self.last_metrics.  A
        transport failure BEFORE the first batch reconnects and replays
        the execute under the shared retry policy; after a batch has
        been yielded the stream cannot be spliced, so it ferries."""
        import random
        import time as _time

        from auron_tpu.ir import serde as ir_serde
        data = task if isinstance(task, (bytes, bytearray)) \
            else ir_serde.serialize(task)
        self.last_metrics: dict = {}
        exec_header = wirecheck.attach_token({"cmd": "execute",
                                              "len": len(data)})
        wirecheck.check_request("engine", exec_header)
        policy = RetryPolicy.from_conf()
        rng = random.Random(policy.seed)
        attempts = max(1, policy.max_attempts)
        attempt = 1
        while True:
            yielded = False
            try:
                fault_point("service.call")
                s = self._ensure_sock()
                send_msg(s, exec_header, data)
                while True:
                    header, payload = recv_msg(s)
                    wirecheck.check_stream_frame("engine", "execute",
                                                 header)
                    t = header.get("type")
                    if t == "batch":
                        yielded = True
                        yield from _batches_from_ipc(payload)
                    elif t == "done":
                        self.last_metrics = header.get("metrics", {})
                        return
                    elif t == "need_resource":
                        self._serve_resource(header.get("key"))
                    elif t == "error":
                        raise RemoteExecutionError(
                            header.get("message", ""),
                            header.get("traceback", ""))
                    else:
                        raise RemoteExecutionError(
                            f"unexpected frame {header!r}")
            except (OSError, EOFError) as e:
                self.close()
                if yielded or attempt >= attempts:
                    if attempt >= attempts:
                        # budget spent here: outer sites must not
                        # multiply the replays (mid-stream failures stay
                        # replayable by a full task re-run)
                        e.auron_retry_exhausted = True  # type: ignore[attr-defined]
                    raise
                delay = policy.backoff_s(attempt, rng)
                log.warning("engine execute to %s:%s failed before first "
                            "batch (attempt %d/%d): %s; retrying in "
                            "%.3fs", self.host, self.port, attempt,
                            attempts, e, delay)
                attempt += 1
                if delay > 0:
                    from auron_tpu.runtime import lockcheck
                    lockcheck.blocked("retry.backoff")
                    _time.sleep(delay)

    def _serve_resource(self, key: str) -> None:
        s = self._ensure_sock()
        src = self._provided.get(str(key))
        if src is None:
            header = wirecheck.attach_token(
                {"cmd": "resource_data", "kind": "missing"})
            wirecheck.check_request("engine", header)
            send_msg(s, header)
            return
        data = _batches_to_ipc(src)
        header = wirecheck.attach_token(
            {"cmd": "resource_data", "kind": "arrow_ipc",
             "len": len(data)})
        wirecheck.check_request("engine", header)
        send_msg(s, header, data)

    def execute(self, task: Any) -> pa.Table:
        batches = list(self.execute_stream(task))
        if not batches:
            return pa.table({})
        return pa.Table.from_batches(batches)

    def shutdown_server(self) -> None:
        s = self._ensure_sock()
        send_msg(s, wirecheck.attach_token({"cmd": "shutdown"}))
        try:
            recv_msg(s)
        except (ConnectionError, OSError, ValueError):
            pass
        self.close()


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description="Auron engine service")
    ap.add_argument("--host", default=None,
                    help="bind host (default: auron.net.bind.host)")
    ap.add_argument("--advertise-host", default=None,
                    help="host advertised in the listening line "
                         "(default: auron.net.advertise.host)")
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args()
    serve(args.host, args.port, advertise_host=args.advertise_host)
