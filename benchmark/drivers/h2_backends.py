"""The h2 cell's backends: h2c servers, all of them in one process.

    python3 h2_backends.py serve --fds FILE [--accepts OUT]
    python3 h2_backends.py serve --listen N [--accepts OUT]

Plain Python, no import of the program (h2_wire.py for the protocol).
`--fds`: listening sockets inherited from the parent, one
`<fd> <identity>` line each; `--listen N`: N listeners of its own on
127.0.0.1 (the client's ceiling). Prints `ready <port>,...` once it
serves. Each stream is answered, once its request has ended, as nginx
answers `GET /` with its stock page: HEADERS (`:status 200`,
content-type, content-length, `x-backend: <identity>` of the listener
the connection came in on, `x-seen-forwarded-for:` the request's
X-Forwarded-For, empty where it has none) and one DATA frame of 612
bytes that ends the stream, under the peer's flow-control windows: the
page with the request's :authority written in front (`h2_wire.page`).

It ends on SIGTERM or when its standard input closes (the parent is
gone), and then writes `--accepts`: the CLOCK_MONOTONIC time of every
connection it accepted, int64 little-endian.
"""
from __future__ import annotations

import argparse
import os
import selectors
import signal
import socket
import sys
import time
from array import array
from collections import deque

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import h2_wire as W  # noqa: E402

RECV = 1 << 18
DEFAULT_WINDOW = 65535


class Conn:
    __slots__ = ("sock", "ident", "peer", "buf", "out", "preface",
                 "window", "initial", "swin", "waiting", "hdrs", "cont",
                 "closed")

    def __init__(self, sock, ident: bytes, peer: str):
        self.sock, self.ident, self.peer = sock, ident, peer
        self.buf, self.out = bytearray(), bytearray()
        self.preface = len(W.PREFACE)
        self.window = self.initial = DEFAULT_WINDOW
        self.swin: dict = {}        # sid -> send window, for open streams
        self.waiting: deque = deque()   # responses held for window
        self.hdrs: dict = {}        # sid -> request headers, body pending
        self.cont = None            # (sid, flags, block) of a CONTINUATION run
        self.closed = False


class Server:
    """h2c on every listener of `listeners` [(socket, identity)], one
    selector loop. `answer(conn, headers) -> (identity, echoed
    X-Forwarded-For)` decides a response (default: the identity of the
    connection's listener and the request's header; `conn.peer` is the
    client's address)."""

    def __init__(self, listeners, answer=None):
        self.sel = selectors.DefaultSelector()
        self.accepts = array("q")
        self.answer = answer
        self.answered = 0
        self._resp: dict = {}       # (identity, xff) -> header block
        for s, ident in listeners:
            s.setblocking(False)
            self.sel.register(s, selectors.EVENT_READ,
                              ("listen", ident.encode()))
        self.stop = False

    def run(self, stdin=None) -> None:
        if stdin is not None:
            self.sel.register(stdin, selectors.EVENT_READ, ("stdin", None))
        while not self.stop:
            for key, ev in self.sel.select(0.5):
                what, x = key.data
                if what == "listen":
                    self._accept(key.fileobj, x)
                elif what == "stdin":
                    if not os.read(key.fd, 4096):
                        self.stop = True
                else:
                    if ev & selectors.EVENT_READ:
                        self._read(x)
                    if ev & selectors.EVENT_WRITE and not x.closed:
                        self._flush(x)

    def close(self) -> None:
        for key in list(self.sel.get_map().values()):
            if key.data[0] != "stdin":
                key.fileobj.close()
        self.sel.close()

    def _accept(self, ls, ident: bytes) -> None:
        while True:
            try:
                s, peer = ls.accept()
            except (BlockingIOError, InterruptedError):
                return
            self.accepts.append(time.perf_counter_ns())
            s.setblocking(False)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            c = Conn(s, ident, peer[0])
            c.out += W.settings([(W.S_MAX_CONCURRENT, 4096)])
            self.sel.register(s, selectors.EVENT_READ, ("conn", c))
            self._flush(c)

    def _drop(self, c: Conn) -> None:
        if not c.closed:
            c.closed = True
            self.sel.unregister(c.sock)
            c.sock.close()

    def _read(self, c: Conn) -> None:
        try:
            data = c.sock.recv(RECV)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            data = b""
        if not data:
            self._drop(c)
            return
        c.buf += data
        if c.preface:
            take = min(c.preface, len(c.buf))
            start = len(W.PREFACE) - c.preface
            if bytes(c.buf[:take]) != W.PREFACE[start:start + take]:
                self._drop(c)
                return
            del c.buf[:take]
            c.preface -= take
            if c.preface:
                return
        try:
            for ftype, flags, sid, payload in W.frames(c.buf):
                self._frame(c, ftype, flags, sid, payload)
        except W.H2WireError:
            c.out += W.frame(W.GOAWAY, 0, 0, bytes(8))
        self._flush(c)

    def _frame(self, c: Conn, ftype, flags, sid, payload) -> None:
        if c.cont is not None:
            csid, cflags, block = c.cont
            if ftype != W.CONTINUATION or sid != csid:
                raise W.H2WireError("expected CONTINUATION")
            block += payload
            if flags & W.F_END_HEADERS:
                c.cont = None
                self._headers(c, csid, cflags, bytes(block))
            return
        if ftype == W.HEADERS:
            block = W.header_block(flags, payload)
            if flags & W.F_END_HEADERS:
                self._headers(c, sid, flags, block)
            else:
                c.cont = (sid, flags, bytearray(block))
        elif ftype == W.DATA:
            if payload:
                c.out += W.window_update(0, len(payload))
            if flags & W.F_END_STREAM and sid in c.hdrs:
                self._respond(c, sid, c.hdrs.pop(sid))
        elif ftype == W.SETTINGS:
            if not flags & W.F_ACK:
                for off in range(0, len(payload) - 5, 6):
                    k = int.from_bytes(payload[off:off + 2], "big")
                    v = int.from_bytes(payload[off + 2:off + 6], "big")
                    if k == W.S_INITIAL_WINDOW:
                        for s in c.swin:
                            c.swin[s] += v - c.initial
                        c.initial = v
                c.out += W.frame(W.SETTINGS, W.F_ACK, 0)
        elif ftype == W.WINDOW_UPDATE:
            inc = int.from_bytes(payload[:4], "big") & 0x7FFFFFFF
            if sid == 0:
                c.window += inc
            elif sid in c.swin:
                c.swin[sid] += inc
            self._release(c)
        elif ftype == W.PING:
            if not flags & W.F_ACK:
                c.out += W.frame(W.PING, W.F_ACK, 0, payload)
        elif ftype == W.RST_STREAM:
            c.hdrs.pop(sid, None)
            c.swin.pop(sid, None)
        elif ftype == W.GOAWAY:
            self._flush(c)
            self._drop(c)

    def _headers(self, c: Conn, sid: int, flags: int, block: bytes) -> None:
        headers = W.decode(block)
        if flags & W.F_END_STREAM:
            self._respond(c, sid, headers)
        else:
            c.hdrs[sid] = headers

    def _respond(self, c: Conn, sid: int, headers: list) -> None:
        xff = auth = b""
        for k, v in headers:
            if k == b"x-forwarded-for":
                xff = v
            elif k == b":authority":
                auth = v
        if self.answer is None:
            ident = c.ident
        else:
            ident, xff = self.answer(c, headers)
        block = self._resp.get((ident, xff))
        if block is None:
            block = self._resp[(ident, xff)] = W.encode([
                (b":status", b"200"), (b"content-type", b"text/html"),
                (b"content-length", b"%d" % len(W.PAGE)),
                (b"x-backend", ident), (b"x-seen-forwarded-for", xff)])
        c.swin[sid] = c.initial
        c.waiting.append((sid, block, W.page(auth)))
        self.answered += 1
        self._release(c)

    def _release(self, c: Conn) -> None:
        """The held responses whose DATA the windows now admit, in
        order."""
        n = len(W.PAGE)
        while c.waiting and c.window >= n:
            sid, block, body = c.waiting[0]
            if c.swin.get(sid, n) < n:
                break
            c.waiting.popleft()
            c.window -= n
            c.swin.pop(sid, None)
            c.out += W.frame(W.HEADERS, W.F_END_HEADERS, sid, block)
            c.out += W.frame(W.DATA, W.F_END_STREAM, sid, body)

    def _flush(self, c: Conn) -> None:
        if c.closed:
            return
        if c.out:
            try:
                sent = c.sock.send(c.out)
                del c.out[:sent]
            except (BlockingIOError, InterruptedError):
                pass
            except OSError:
                self._drop(c)
                return
        self.sel.modify(c.sock, selectors.EVENT_READ
                        | (selectors.EVENT_WRITE if c.out else 0),
                        ("conn", c))


def _term(srv: Server):
    def handler(_sig, _frame):
        srv.stop = True
    return handler


def serve(a) -> int:
    listeners = []
    if a.fds:
        with open(a.fds) as f:
            for ln in f.read().splitlines():
                fd, ident = ln.split(" ", 1)
                listeners.append((socket.socket(fileno=int(fd)), ident))
    else:
        for _ in range(a.listen):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            s.listen(1024)
            listeners.append((s, f"b|127.0.0.1:{s.getsockname()[1]}"))
    srv = Server(listeners)
    signal.signal(signal.SIGTERM, _term(srv))
    print("ready " + ",".join(str(s.getsockname()[1])
                              for s, _i in listeners), flush=True)
    try:
        srv.run(sys.stdin.fileno())
    finally:
        if a.accepts:
            with open(a.accepts, "wb") as f:
                acc = srv.accepts
                if sys.byteorder != "little":
                    acc.byteswap()
                acc.tofile(f)
        srv.close()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)
    s = sub.add_parser("serve")
    src = s.add_mutually_exclusive_group(required=True)
    src.add_argument("--fds")
    src.add_argument("--listen", type=int)
    s.add_argument("--accepts")
    return serve(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
