"""One writer for every token stream of a server.

A streaming ``POST /generate`` used to be written by its own handler
thread, woken once a token: with 128 streams, 128 threads that each want
the interpreter lock four or five times for one 30-byte line, against the
generation loop that has to take it back from them at every step
(``PERF.md``, PR 32).  Here one thread writes them all.  The loop hands it
one batch a step (:meth:`StreamWriter.take`, the ``sink`` of
:meth:`~mxnet_tpu.serving.generate.GenerationEngine.submit`), it formats
each line and sends it on that stream's socket, and a handler thread
sleeps from its headers to its stream's last token line.

No stream holds up another: a send never blocks.  What a socket does not
take stays with that stream, in order, and goes out when the selector says
the socket takes bytes again; a stream whose client is gone is dropped
while the engine finishes it on its own.

Everything a stream's state holds is touched by the writer thread alone.
The other threads reach it through one inbox: the loop's batches, a
handler's :meth:`~StreamWriter.attach` and :meth:`~StreamWriter.detach`.
"""
from __future__ import annotations

import collections
import selectors
import socket
import threading
import time

__all__ = ["StreamWriter"]

# how long a handler or stop() waits for the writer to let go
_LET_GO_S = 5.0

# GenerationStream.wire of a stream whose socket the writer has let go of:
# what still comes for it is thrown away
_DROPPED = object()


class _Wire:
    """One attached stream: its socket, the index of its next token line,
    and what the socket has not taken yet, oldest first, as ``(unsent
    bytes, emit stamp, ns spent writing it so far)``.  ``released`` is set,
    with ``outcome`` saying why, when the writer will not touch the socket
    again: ``"done"`` (the last token line is out and the stream has
    ended), ``"gone"`` (the client hung up), ``"detached"`` (the handler
    took it back) or ``"closed"`` (the writer stopped or died)."""

    __slots__ = ("stream", "sock", "index", "backlog", "ended", "last_ns",
                 "released", "outcome")

    def __init__(self, stream, sock):
        self.stream = stream
        self.sock = sock
        self.index = 0
        self.backlog = collections.deque()
        self.ended = False
        # when the engine last had something for it, perf_counter_ns: the
        # handler's measure of a generation that has stopped producing
        self.last_ns = time.perf_counter_ns()
        self.released = threading.Event()
        self.outcome = None


class StreamWriter:
    """The thread that puts token lines on sockets, for one server.

    ``metrics`` is the engine's
    :class:`~mxnet_tpu.serving.generate.GenerationMetrics`: the writer adds
    ``emit_to_wire_us``, ``stream_write_us``, ``stream_tokens_written`` and
    ``stream_writer_wakes`` once a wake."""

    def __init__(self, metrics):
        self._metrics = metrics
        self._inbox = collections.deque()
        self._sel = selectors.DefaultSelector()
        # a byte on this pair is "the inbox has something"
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._sel.register(self._wake_r, selectors.EVENT_READ, None)
        self._attached = set()
        # wires whose last token line is out, until the counters have it
        self._finished = []
        self._stopped = False       # no wire is taken on any more
        self._thread = None
        self._wire_ns = self._write_ns = self._written = 0

    # -- any thread ----------------------------------------------------------
    def start(self):
        if self._thread is None and not self._stopped:
            self._thread = threading.Thread(
                target=self._run, name="mxnet-tpu-stream-writer",
                daemon=True)
            self._thread.start()
        return self

    def _send_msg(self, msg):
        if self._stopped:
            return      # nobody reads the inbox any more
        self._inbox.append(msg)
        try:
            self._wake_w.send(b"\0")
        except OSError:
            # a full pipe is a wake already on its way; a closed one is a
            # writer that has stopped, which attach() and close() answer
            pass

    def take(self, batch):
        """The loop's tokens of one step, ``(stream, token, emit stamp)``
        each, a token of None being its stream's end.  Never blocks."""
        self._send_msg(batch)

    def attach(self, stream, sock):
        """From here on ``sock`` is the writer's: what the engine has
        emitted for ``stream`` so far and whatever follows goes out on it,
        without blocking.  Returns the stream's wire; wait on its
        ``released`` before touching the socket again."""
        wire = _Wire(stream, sock)
        sock.setblocking(False)
        self._send_msg(("attach", wire))
        if self._stopped:
            # nobody may be reading the inbox any more; release() is
            # idempotent, so a writer that did see the message does no harm
            self._release(wire, "closed")
        return wire

    def detach(self, wire):
        """Take a wire's socket back before its stream has ended.  True
        once the writer has let go of it."""
        self._send_msg(("detach", wire))
        return wire.released.wait(_LET_GO_S)

    def close(self):
        """Write out what the loop has handed over, let go of every
        socket and end the thread."""
        self._send_msg(("stop",))
        thread, self._thread = self._thread, None
        if thread is None:
            self._shut()
        else:
            thread.join(_LET_GO_S)

    # -- the writer thread ---------------------------------------------------
    def _run(self):
        try:
            while not self._stopped:
                for key, _mask in self._sel.select():
                    if key.data is None:
                        try:
                            self._wake_r.recv(4096)
                        except BlockingIOError:
                            pass
                    else:
                        self._drain(key.data)
                self._read_inbox()
        finally:
            # also when it dies: every attached stream then fails typed at
            # its handler, and attach() refuses the later ones
            self._shut()

    def _shut(self):
        self._stopped = True
        for wire in list(self._attached):
            self._release(wire, "closed")
        while self._inbox:
            msg = self._inbox.popleft()
            if msg.__class__ is tuple and msg[0] == "attach":
                self._release(msg[1], "closed")
        self._sel.close()
        self._wake_r.close()
        self._wake_w.close()

    def _read_inbox(self):
        woke = 0
        while self._inbox and not self._stopped:
            msg = self._inbox.popleft()
            if msg.__class__ is list:
                woke = 1
                for stream, token, t_emit in msg:
                    self._item(stream, token, t_emit)
            elif msg[0] == "attach":
                self._attach(msg[1])
            elif msg[0] == "detach":
                self._release(msg[1], "detached")
            else:
                self._stopped = True
        if woke or self._written:
            self._metrics.add(
                emit_to_wire_us=self._wire_ns // 1000,
                stream_write_us=self._write_ns // 1000,
                stream_tokens_written=self._written,
                stream_writer_wakes=woke)
            # the counters are whole microseconds: the rest waits
            self._wire_ns %= 1000
            self._write_ns %= 1000
            self._written = 0
        # a handler wakes, and its client reads the final line, only after
        # the counters hold every token line of the stream
        while self._finished:
            self._release(self._finished.pop(), "done")

    def _attach(self, wire):
        early, wire.stream.wire = wire.stream.wire, wire
        self._attached.add(wire)
        for token, t_emit in early or ():
            self._item(wire.stream, token, t_emit)

    def _item(self, stream, token, t_emit):
        wire = stream.wire
        if wire.__class__ is not _Wire:
            if wire is None:
                stream.wire = [(token, t_emit)]
            elif wire is not _DROPPED:
                wire.append((token, t_emit))    # emitted before its attach
            return
        wire.last_ns = t_emit
        if token is None:
            wire.ended = True
            if not wire.backlog:
                self._finished.append(wire)
            return
        t0 = time.perf_counter_ns()
        # byte for byte json.dumps({"token": token, "index": index}) + "\n"
        line = b'{"token": %d, "index": %d}\n' % (token, wire.index)
        wire.index += 1
        if not wire.backlog:
            try:
                sent = wire.sock.send(line)
            except BlockingIOError:
                sent = 0
            except OSError:
                # the client hung up; the engine finishes on its own
                self._release(wire, "gone")
                return
            if sent == len(line):
                t1 = time.perf_counter_ns()
                self._wire_ns += t1 - t_emit
                self._write_ns += t1 - t0
                self._written += 1
                return
            line = line[sent:]
            self._sel.register(wire.sock, selectors.EVENT_WRITE, wire)
        wire.backlog.append((line, t_emit, time.perf_counter_ns() - t0))

    def _drain(self, wire):
        """The socket of a stream that was behind takes bytes again."""
        backlog = wire.backlog
        while backlog:
            line, t_emit, spent = backlog[0]
            t0 = time.perf_counter_ns()
            try:
                sent = wire.sock.send(line)
            except BlockingIOError:
                return
            except OSError:
                self._release(wire, "gone")
                return
            t1 = time.perf_counter_ns()
            if sent < len(line):
                backlog[0] = (line[sent:], t_emit, spent + t1 - t0)
                return
            backlog.popleft()
            self._wire_ns += t1 - t_emit
            self._write_ns += spent + t1 - t0
            self._written += 1
        self._sel.unregister(wire.sock)
        if wire.ended:
            self._finished.append(wire)

    def _release(self, wire, outcome):
        if wire.released.is_set():
            return
        wire.outcome = outcome
        if wire in self._attached:
            self._attached.discard(wire)
            wire.stream.wire = _DROPPED
            if wire.backlog:
                self._sel.unregister(wire.sock)
                wire.backlog.clear()
        wire.released.set()
