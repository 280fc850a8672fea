"""Minimal threaded HTTP front-end (stdlib only) over the batcher.

Wire format (JSON + base64 tensor payloads — the npz-ish convention):

``POST /predict``::

    {"inputs": [{"data": <b64 raw bytes>, "shape": [...], "dtype": "f4"}],
     "deadline_ms": 100}            # optional

-> ``{"outputs": [<same tensor encoding>], "latency_ms": ...}``

Degradation maps to status codes: 429 = admission-control fast-reject
(queue full — retry with backoff), 504 = deadline exceeded / shed,
503 = server shutting down (retryable elsewhere), 400 = malformed
request, 500 = model error.  ``GET /stats`` returns the
metrics snapshot, ``GET /healthz`` a liveness probe.

This is a loopback demo/test front-end, not a hardened edge server —
the real production story is the engine/batcher behind any RPC layer.
"""
from __future__ import annotations

import base64
import json
import socket as _socket
import sys as _sys
import threading
import time
from concurrent.futures import TimeoutError as _FutTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as onp

from .batcher import DynamicBatcher
from .errors import (DeadlineExceededError, EngineClosedError,
                     QueueFullError)
from .stream_writer import StreamWriter

__all__ = ["ModelServer", "encode_array", "decode_array"]

_DEFAULT_RESULT_TIMEOUT_S = 30.0


def _dtype_token(dt):
    # ml_dtypes customs (bfloat16, float8_*) stringify as anonymous void
    # ('<V2'...) which does NOT round-trip through onp.dtype(); their
    # .name does. Native dtypes keep the endian-explicit .str.
    return dt.name if dt.kind == "V" else dt.str


def _resolve_dtype(token):
    try:
        return onp.dtype(token)
    except TypeError:
        import ml_dtypes
        return onp.dtype(getattr(ml_dtypes, token))


def encode_array(arr):
    arr = onp.ascontiguousarray(arr)
    return {"data": base64.b64encode(arr.tobytes()).decode("ascii"),
            "shape": list(arr.shape), "dtype": _dtype_token(arr.dtype)}


def decode_array(obj):
    arr = onp.frombuffer(base64.b64decode(obj["data"]),
                         dtype=_resolve_dtype(obj["dtype"]))
    return arr.reshape(obj["shape"]).copy()


def _net_request_fault():
    """THE ``net.request`` wire-point site for this module (the fault
    registry wants one literal site per name; /predict and /generate
    share the same inbound wire)."""
    from .. import faults as _faults
    return _faults.wire_point("net.request")


def _net_response_fault():
    """THE ``net.response`` wire-point site for this module."""
    from .. import faults as _faults
    return _faults.wire_point("net.response")


def try_reply(handler, code, payload, **dump_kwargs):
    """Run the handler's ``_reply`` unless the peer already hung up
    (dead-socket replies are swallowed; the handler's bookkeeping
    continues) — the ONE broken-pipe policy shared by the replica front
    here and the fleet's ``RouterServer``."""
    try:
        handler._reply(code, payload, **dump_kwargs)
    except (BrokenPipeError, ConnectionResetError):
        handler.close_connection = True


class _Handler(BaseHTTPRequestHandler):
    # HTTP/1.1: responses always carry Content-Length (or explicitly
    # close), so connections persist across requests — the wire half of
    # the zero-hop data path (docs/SERVING.md).  ``timeout`` is the idle
    # reaper: socketserver arms it on the socket, and a keep-alive
    # connection with no request for that long is closed by the stdlib
    # handle loop (socket.timeout -> close_connection).
    protocol_version = "HTTP/1.1"
    # header flush + body write are separate sends: without TCP_NODELAY
    # the Nagle/delayed-ACK interaction stalls the pair ~40 ms per
    # reply on a persistent connection
    disable_nagle_algorithm = True

    def setup(self):
        self.timeout = getattr(self.server, "idle_timeout_s", None)
        if self.timeout is None:
            from ..util import getenv as _getenv
            self.timeout = float(_getenv("MXNET_HTTP_IDLE_S"))
        super().setup()

    # quiet: per-request stderr logging would swamp load tests
    def log_message(self, fmt, *args):   # noqa: A003
        pass

    def _drain_body(self):
        """Consume the request body on paths that reply without reading
        it (404s, bad routes).  Under keep-alive an unread body would be
        parsed as the NEXT request on the persistent connection."""
        length = int(self.headers.get("Content-Length") or 0)
        if length > 0:
            try:
                self.rfile.read(length)
            except OSError:
                self.close_connection = True

    def _reply(self, code, payload, **dump_kwargs):
        self._reply_text(code, json.dumps(payload, **dump_kwargs),
                         "application/json")

    def _reply_text(self, code, text, ctype):
        body = text.encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        if getattr(self.server, "draining", False):
            # drain-aware close: during stop() every reply tells the
            # peer to re-dial elsewhere instead of parking the
            # connection against a dying server
            self.send_header("Connection", "close")
        self.end_headers()
        self.wfile.write(body)

    def _try_reply(self, code, payload, **dump_kwargs):
        """Reply unless the peer already hung up — a deadline-capped
        client disconnecting mid-wait is routine, and the request's
        bookkeeping (trace spool, metrics) must survive the dead socket
        instead of dying on a BrokenPipeError."""
        try_reply(self, code, payload, **dump_kwargs)

    def _reply_torn(self, code, payload, nbytes):
        """Injected ``torn(nbytes)`` response: headers advertise the full
        body, only ``nbytes`` bytes follow, and the connection closes —
        the peer sees an IncompleteRead, exactly what a connection dying
        mid-response looks like on a real wire."""
        body = json.dumps(payload).encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body[:max(0, int(nbytes))])
        self.close_connection = True

    def do_GET(self):                    # noqa: N802
        if self.path == "/healthz":
            self._reply(200, {"status": "ok"})
        elif self.path == "/stats":
            stats = self.server.batcher.stats()
            gen = getattr(self.server, "generator", None)
            if gen is not None:
                stats["generate"] = gen.metrics.stats()
            self._reply(200, stats)
        elif self.path == "/metrics":
            # Prometheus text exposition over the process-wide telemetry
            # registry — serving, engine, io, faults and compile metrics
            # in one scrape (docs/OBSERVABILITY.md)
            from .. import telemetry as _telemetry
            self._reply_text(200, _telemetry.prometheus_text(),
                             "text/plain; version=0.0.4; charset=utf-8")
        elif self.path == "/statusz":
            from .. import telemetry as _telemetry
            payload = _telemetry.statusz_payload()
            payload["serving"] = self.server.batcher.stats()
            engine = getattr(self.server.batcher, "engine", None)
            if engine is not None and \
                    hasattr(engine, "compile_passes_info"):
                # which rewrite pipeline (if any) built this replica's
                # programs — the per-model serving-mode surface the
                # fleet federates (docs/COMPILE_PASSES.md)
                payload["compile_passes"] = engine.compile_passes_info()
            # default=str: safety net for odd telemetry values only — the
            # wire endpoints (/predict, /stats) must keep raising loudly
            # on a non-serializable payload, not silently stringify it
            self._reply(200, payload, default=str)
        else:
            self._reply(404, {"error": "not_found", "path": self.path})

    def do_POST(self):                   # noqa: N802
        # in-flight accounting: stop() drains these before the batcher
        # dies, so a shutdown mid-request finishes the response instead
        # of severing it
        srv = self.server
        with srv.inflight_cv:
            srv.inflight += 1
        try:
            self._do_POST()
        finally:
            with srv.inflight_cv:
                srv.inflight -= 1
                srv.inflight_cv.notify_all()

    def _do_POST(self):
        from .. import telemetry as _telemetry
        if self.path == "/generate":
            self._do_generate()
            return
        if self.path != "/predict":
            self._drain_body()
            self._reply(404, {"error": "not_found", "path": self.path})
            return
        # wire-level chaos on the inbound request (docs/RESILIENCE.md
        # net.* registry): `delay` slept inside the point; reset/torn/
        # blackhole abandon the exchange without a reply — the peer sees
        # a dead connection, never a clean HTTP error
        if _net_request_fault() is not None:
            self.close_connection = True
            return
        # request tracing (docs/OBSERVABILITY.md): the wire's `trace`
        # field is continued through parse -> batcher -> engine ->
        # serialize, and the 200 response carries the breakdown back
        t_wall0 = _telemetry._wall_us() if _telemetry.tracing_enabled() \
            else 0
        trace = _telemetry.NULL_TRACE

        def spool():
            if trace:
                _telemetry.maybe_spool(
                    trace, (_telemetry._wall_us() - t_wall0) / 1000.0,
                    role="replica")

        try:
            length = int(self.headers.get("Content-Length", "0"))
            req = json.loads(self.rfile.read(length))
            trace = _telemetry.continue_trace(req.get("trace"))
            inputs = tuple(decode_array(o) for o in req["inputs"])
            deadline_ms = req.get("deadline_ms")
            if deadline_ms is not None:
                # coerce here so a non-numeric value is a 400, not a
                # TypeError deep in the batcher misreported as 500
                deadline_ms = float(deadline_ms)
            if trace:
                # wire + accept-queue gap (router sent_us -> this
                # handler) then the decode itself
                trace.accept_span("replica_accept", t_wall0)
                trace.add_span("replica_parse", t_wall0,
                               _telemetry._wall_us() - t_wall0,
                               bytes=length)
        except Exception as e:           # noqa: BLE001
            self._reply(400, {"error": "bad_request", "detail": str(e)})
            return

        batcher = self.server.batcher
        t0 = time.perf_counter()
        try:
            fut = batcher.submit(inputs, deadline_ms=deadline_ms,
                                 trace=trace)
            wait_s = (deadline_ms / 1000.0 + 1.0) \
                if deadline_ms is not None else _DEFAULT_RESULT_TIMEOUT_S
            out = fut.result(timeout=wait_s)
        except QueueFullError as e:
            trace.mark("shed")           # admission reject: always keep
            self._try_reply(429, {"error": "queue_full",
                            "detail": str(e)})
            spool()
            return
        except DeadlineExceededError as e:
            trace.mark("shed")
            self._try_reply(504, {"error": "deadline_exceeded",
                            "detail": str(e)})
            spool()
            return
        except (_FutTimeout, TimeoutError):
            # nobody is waiting anymore: cancel so a still-queued request
            # is skipped at dispatch instead of burning a batch slot
            fut.cancel()
            batcher.metrics.inc("timeouts")
            self._try_reply(504, {"error": "result_timeout"})
            spool()
            return
        except EngineClosedError as e:
            # routine shutdown/restart, not a model bug: retryable
            self._try_reply(503, {"error": "unavailable",
                            "detail": str(e)})
            spool()
            return
        except Exception as e:           # noqa: BLE001
            self._try_reply(500, {"error": "model_error",
                            "detail": str(e)})
            spool()
            return
        outs = out if isinstance(out, tuple) else (out,)
        t_ser0 = _telemetry._wall_us() if trace else 0
        encoded = [encode_array(o) for o in outs]
        resp = {"outputs": encoded,
                "latency_ms": round((time.perf_counter() - t0) * 1000.0, 3)}
        if trace:
            import os as _os
            trace.add_span("reply_serialize", t_ser0,
                           _telemetry._wall_us() - t_ser0)
            resp["trace"] = trace.response_payload(
                proc=f"replica:{_os.getpid()}")
        # wire-level chaos on the outbound response: `torn(nbytes)`
        # truncates the body mid-write (the peer reads an incomplete
        # payload off a closed socket), reset/blackhole swallow it
        act = _net_response_fault()
        if act is not None and act.kind == "torn":
            self._reply_torn(200, resp, act.nbytes)
        elif act is not None:
            self.close_connection = True
        else:
            self._try_reply(200, resp)
        spool()

    def _do_generate(self):
        """``POST /generate``: KV-cached generation through the server's
        :class:`~mxnet_tpu.serving.generate.GenerationEngine`.

        Request: ``{"tokens": [...], "max_new_tokens": N, "eos_id": id,
        "stream": bool, "trace": {...}}``.  Non-streaming replies one
        JSON body.  ``"stream": true`` replies JSONL over a
        close-delimited body (no Content-Length — the HTTP/1.0 framing
        a line-reading client consumes as the tokens land): one
        ``{"token": t, "index": i}`` line per token, then a final
        ``{"done": true, "tokens": [...], "ttft_ms": ...,
        "tokens_per_s": ..., "finish_reason": ..., "trace": ...}`` line
        (or ``{"error": ...}`` if the generation died mid-stream)."""
        import os as _os
        from .. import telemetry as _telemetry
        from .errors import ServingError
        gen = getattr(self.server, "generator", None)
        if gen is None:
            self._reply(404, {"error": "generation_not_enabled"})
            return
        if _net_request_fault() is not None:
            self.close_connection = True
            return
        t_wall0 = _telemetry._wall_us() if _telemetry.tracing_enabled() \
            else 0
        trace = _telemetry.NULL_TRACE
        try:
            length = int(self.headers.get("Content-Length", "0"))
            req = json.loads(self.rfile.read(length))
            trace = _telemetry.continue_trace(req.get("trace"))
            tokens = [int(t) for t in req["tokens"]]
            max_new = int(req.get("max_new_tokens", 32))
            eos_id = req.get("eos_id")
            streaming = bool(req.get("stream", False))
            if trace:
                trace.accept_span("replica_accept", t_wall0)
        except Exception as e:           # noqa: BLE001
            self._reply(400, {"error": "bad_request", "detail": str(e)})
            return

        t0 = time.perf_counter()
        # a streamed request's tokens go to the server's one writer, a step
        # at a time; any other's stay on the stream's own queue
        writer = self.server.stream_writer
        try:
            stream = gen.submit(tokens, max_new_tokens=max_new,
                                eos_id=eos_id, trace=trace,
                                sink=writer if streaming else None)
        except QueueFullError as e:
            trace.mark("shed")
            self._try_reply(429, {"error": "queue_full", "detail": str(e)})
            return
        except EngineClosedError as e:
            self._try_reply(503, {"error": "unavailable", "detail": str(e)})
            return
        except ServingError as e:        # bad prompt (too long / empty)
            self._reply(400, {"error": "bad_request", "detail": str(e)})
            return

        def final_payload(result):
            resp = dict(result)
            resp["done"] = True
            resp["latency_ms"] = round(
                (time.perf_counter() - t0) * 1000.0, 3)
            if trace:
                resp["trace"] = trace.response_payload(
                    proc=f"replica:{_os.getpid()}")
            return resp

        def spool():
            if trace:
                _telemetry.maybe_spool(
                    trace, (time.perf_counter() - t0) * 1000.0,
                    role="replica")

        if not streaming:
            try:
                result = stream.result(timeout=_DEFAULT_RESULT_TIMEOUT_S)
            except TimeoutError:
                self._try_reply(504, {"error": "result_timeout"})
                spool()
                return
            except Exception as e:       # noqa: BLE001
                self._try_reply(500, {"error": "model_error",
                                "detail": str(e)})
                spool()
                return
            act = _net_response_fault()
            if act is not None and act.kind == "torn":
                self._reply_torn(200, final_payload(result), act.nbytes)
            elif act is not None:
                self.close_connection = True
            else:
                self._try_reply(200, final_payload(result))
            spool()
            return

        # -- streaming: close-delimited JSONL ----------------------------
        # wire chaos applies to the whole response stream: any injected
        # net.response fault tears the connection (a torn byte-count has
        # no meaning on an unframed stream — truncation IS the fault)
        if _net_response_fault() is not None:
            self.close_connection = True
            spool()
            return
        try:
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Connection", "close")
            self.end_headers()
            # the token lines are the writer's; this thread sleeps until
            # the stream's last one is out (or the socket comes back to it
            # for another reason) and writes the final line
            self._await_wire(writer, writer.attach(stream, self.connection))
            final = final_payload(
                stream.result(timeout=_DEFAULT_RESULT_TIMEOUT_S))
        except (BrokenPipeError, ConnectionResetError):
            # client hung up mid-stream; the engine finishes on its own
            self.close_connection = True
            spool()
            return
        except Exception as e:           # noqa: BLE001
            # generation died AFTER the 200 + some tokens went out: the
            # only honest wire move on an unframed stream is a typed
            # error line (the client raises GenerationStreamBroken)
            final = {"error": "stream_broken", "detail": str(e),
                     "trace_id": trace.trace_id if trace else None}
        try:
            self.wfile.write(json.dumps(final).encode() + b"\n")
            self.wfile.flush()
        except OSError:
            pass        # hung up, or not reading, at the very end
        self.close_connection = True
        spool()

    def _await_wire(self, writer, wire):
        """Sleep until the writer has put out the last token line of
        ``wire``'s stream and let go of its socket: one wake a request.
        Raises what else ended the wait: ``BrokenPipeError`` (the client
        hung up), ``TimeoutError`` (the engine has had nothing for the
        stream for ``_DEFAULT_RESULT_TIMEOUT_S``; the socket is taken
        back) or ``EngineClosedError`` (the writer stopped or died).  The
        socket is this thread's again, and blocking, in every case but a
        writer that does not give it back, which reads as a client gone:
        nobody may write on it then."""
        wait_s = _DEFAULT_RESULT_TIMEOUT_S
        while not wire.released.wait(wait_s):
            idle_s = (time.perf_counter_ns() - wire.last_ns) / 1e9
            wait_s = _DEFAULT_RESULT_TIMEOUT_S - idle_s
            if wait_s <= 0 and not writer.detach(wire):
                raise BrokenPipeError("the stream writer holds the socket")
        self.connection.settimeout(self.timeout)
        if wire.outcome == "gone":
            raise BrokenPipeError("client hung up mid-stream")
        if wire.outcome == "detached":
            raise TimeoutError("no token within timeout")
        if wire.outcome == "closed":
            raise EngineClosedError("the server's stream writer stopped")


class _FleetHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a fleet-sized accept backlog.

    The stdlib default ``request_queue_size`` is 5: under a router
    fanning tens of dispatch (and hedge) threads at a replica, SYNs
    overflow the listen backlog and the client pays the kernel's ~1 s
    retransmit — a latency cliff that looks exactly like a slow replica
    and trips breakers for no reason.  A deeper backlog absorbs the
    connection bursts the fleet actually produces (admission control
    still sheds at the batcher, where it is observable).

    Accepted connections are tracked so :meth:`sever_idle` can close the
    keep-alive connections still parked against a stopping server —
    without it, every parked peer holds a handler thread (and fd) alive
    for up to the idle timeout after ``stop()``, and a restart on the
    same port leaves ghosts of the old server answering requests."""

    request_queue_size = 128

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._live_conns = set()
        self._live_lock = threading.Lock()

    def get_request(self):
        sock, addr = super().get_request()
        with self._live_lock:
            self._live_conns.add(sock)
        return sock, addr

    def handle_error(self, request, client_address):
        exc = _sys.exc_info()[1]
        if isinstance(exc, (BrokenPipeError, ConnectionResetError)):
            return      # peer hung up (or stop() severed the socket)
        super().handle_error(request, client_address)

    def shutdown_request(self, request):
        with self._live_lock:
            self._live_conns.discard(request)
        super().shutdown_request(request)

    def sever_idle(self):
        """Close every connection still open against this server.  Call
        only after in-flight requests have drained: what remains are
        keep-alive peers parked between requests, whose handler threads
        wake with EOF and exit."""
        with self._live_lock:
            conns = list(self._live_conns)
        for sock in conns:
            try:
                sock.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass


class ModelServer:
    """Loopback HTTP server wrapping a :class:`DynamicBatcher`.

    ``port=0`` picks an ephemeral port (read it back via ``.port``).
    ``start()`` launches both the batcher and the accept loop;
    ``stop()`` tears both down.  Usable as a context manager.

    ``generator`` (optional): a
    :class:`~mxnet_tpu.serving.generate.GenerationEngine` serving
    ``POST /generate`` next to the batcher's ``/predict`` — one replica
    process can front both the one-shot and the token-streaming path.
    """

    def __init__(self, batcher, host="127.0.0.1", port=0, generator=None,
                 idle_timeout_s=None):
        if not isinstance(batcher, DynamicBatcher):
            batcher = DynamicBatcher(batcher)
        self.batcher = batcher
        self.generator = generator
        self._httpd = _FleetHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self._httpd.draining = False
        self._httpd.idle_timeout_s = idle_timeout_s
        # stop() does its own BOUNDED drain below; block_on_close would
        # make server_close() join handler threads with no timeout, so a
        # wedged request could hang shutdown forever
        self._httpd.block_on_close = False
        self._httpd.batcher = batcher
        self._httpd.generator = generator
        # one thread writes the token lines of every streamed /generate
        self._writer = StreamWriter(generator.metrics) \
            if generator is not None else None
        self._httpd.stream_writer = self._writer
        self._httpd.inflight = 0
        self._httpd.inflight_cv = threading.Condition()
        self._thread = None
        self._closed = False

    @property
    def host(self):
        return self._httpd.server_address[0]

    @property
    def port(self):
        return self._httpd.server_address[1]

    @property
    def url(self):
        return f"http://{self.host}:{self.port}"

    def start(self):
        if self._closed:
            # stop() closed the listening socket; serve_forever on it would
            # die silently in the daemon thread and refuse every connection
            raise EngineClosedError(
                "ModelServer stopped; construct a new one to serve again")
        self.batcher.start()
        if self._writer is not None:
            self._writer.start()
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._httpd.serve_forever,
                name="mxnet-tpu-http", daemon=True)
            self._thread.start()
        return self

    def stop(self, drain_s=10.0):
        """Graceful drain, then teardown.

        The listening socket closes first (new connections are refused —
        a retrying client rides out the window), then in-flight requests
        get up to ``drain_s`` seconds to finish THROUGH the still-running
        batcher, and only then does the batcher die — so a stop
        mid-request completes the active response instead of severing
        it.  Requests still wedged past the budget are failed by
        ``batcher.stop()`` (their handlers reply 503 and exit).  A
        stopped server stays unrestartable: construct a new one.
        """
        self._closed = True
        # drain-aware close: from here on every reply (including the
        # in-flight ones finishing below) carries Connection: close, so
        # keep-alive peers stop parking connections against this server
        self._httpd.draining = True
        if self._thread is not None:
            self._httpd.shutdown()
            self._thread.join(5.0)
            self._thread = None
        self._httpd.server_close()
        deadline = time.monotonic() + max(0.0, float(drain_s))
        with self._httpd.inflight_cv:
            while self._httpd.inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._httpd.inflight_cv.wait(remaining)
        if self.generator is not None:
            self.generator.stop()
            # behind the engine: what it emitted while draining is written
            # out before the writer lets go of the sockets and is joined
            self._writer.close()
        self.batcher.stop()
        # in-flight work is done (or failed by batcher.stop above) —
        # what's left are idle keep-alive peers; sever them so no
        # handler thread outlives the server
        self._httpd.sever_idle()
        # buffered trace-spool records must survive a graceful worker
        # stop (the chaos-kill path relies on the periodic flush instead)
        from .. import telemetry as _telemetry
        _telemetry.flush_trace_spool()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
