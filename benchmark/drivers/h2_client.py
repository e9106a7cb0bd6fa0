"""The clients of `h2_closed_loop`: h2load's `-c C -m S`, a process of
their own.

    python3 h2_client.py client --server 127.0.0.1:PORT --sources IP,...
        --streams S --pool POOL --seq SEQ --idents IDENTS --out OUT
        --drain S
    python3 h2_client.py ceiling --seconds S [--connections C --streams S]

Plain Python, no import of the program (h2_wire.py for the protocol).
`client` opens one h2c connection (prior knowledge) from each source
address of `--sources`, and keeps `--streams` streams open on each: a
stream that ends starts the next one of that connection's row of the
sequence at once (a closed loop). A request is `GET /` with the pool
entry's `:authority`; a stream ends with END_STREAM or RST_STREAM, and
its body has to be `h2_wire.page(<its :authority>)` whole (else
BODY_WRONG: cut short, or another request's). Its send and its
end are stamped with `time.perf_counter_ns()`, CLOCK_MONOTONIC on
Linux, which the processes share.

`client` prints `ready` once its connections are open and their
requests encoded, then runs. A line `until <ns>` on its standard input
sets the time after which it starts no stream; it then waits up to
`--drain` seconds for the streams open, writes what it recorded to
`--out` (see `write_results`) and prints `done <streams>`. SIGTERM, or
its standard input closing, ends it the same way at once.

`POOL`: one `<authority>` line a pool rank. `SEQ`: int32 little-endian,
one row of pool ranks a connection. `IDENTS`: one backend identity a
line; a response's `x-backend` is recorded as its line number.
"""
from __future__ import annotations

import argparse
import os
import selectors
import shutil
import signal
import socket
import struct
import subprocess
import sys
import tempfile
import time
from array import array

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import h2_wire as W  # noqa: E402

RECV = 1 << 18
STREAM_WINDOW = 1 << 20         # per stream: a response is 612 bytes
TOP_UP = 1 << 30                # connection window given back in steps
# `ident` of a stream without a usable answer
NO_IDENT, UNKNOWN_IDENT = -1, -2
# `flags` bits
RESET, XFF_WRONG, NOT_200, BODY_WRONG = 1, 2, 4, 8


class _Term(Exception):
    pass


def _term(_sig, _frame):
    raise _Term()


class Conn:
    __slots__ = ("sock", "src", "row", "pos", "next_sid", "open", "body",
                 "buf", "out", "got", "dead")

    def __init__(self, sock, src: bytes, row):
        self.sock, self.src, self.row = sock, src, row
        self.pos = 0                # next draw of its row
        self.next_sid = 1
        self.open: dict = {}        # sid -> k
        self.body: dict = {}        # sid -> DATA received, open streams
        self.buf, self.out = bytearray(), bytearray()
        self.got = 0                # DATA bytes since the last top-up
        self.dead = False


def load(pool_path: str, seq_path: str, idents_path: str, conns: int):
    with open(pool_path) as f:
        pages = [W.page(ln.encode()) for ln in f.read().splitlines()]
        f.seek(0)
        blocks = [W.encode([(b":method", b"GET"), (b":scheme", b"http"),
                            (b":path", b"/"), (b":authority", ln.encode())])
                  for ln in f.read().splitlines()]
    seq = array("i")
    with open(seq_path, "rb") as f:
        seq.frombytes(f.read())
    if sys.byteorder != "little":
        seq.byteswap()
    per = len(seq) // conns
    rows = [seq[c * per:(c + 1) * per] for c in range(conns)]
    with open(idents_path) as f:
        idents = {ln.encode(): k for k, ln in enumerate(f.read().splitlines())}
    return blocks, pages, rows, idents


def closed_loop(conns: list, blocks: list, pages: list, idents: dict,
                streams: int, drain_s: float) -> dict:
    """-> the records by stream k (arrays): pool rank, send and end
    times (t_done 0 = never ended), identity index, flags."""
    clock = time.perf_counter_ns
    rank, t_sub, t_done = array("i"), array("q"), array("q")
    ident, flags = array("i"), array("b")
    sel = selectors.DefaultSelector()
    for c in conns:
        sel.register(c.sock, selectors.EVENT_READ, c)
    stdin = sys.stdin.fileno()
    sel.register(stdin, selectors.EVENT_READ, None)
    state = {"until": 1 << 62, "stop": False}
    hdr_flags = bytes((W.HEADERS, W.F_END_STREAM | W.F_END_HEADERS))

    def start(c: Conn) -> None:
        r = c.row[c.pos % len(c.row)]
        c.pos += 1
        sid = c.next_sid
        c.next_sid += 2
        k = len(rank)
        rank.append(r)
        ident.append(NO_IDENT)
        flags.append(0)
        t_done.append(0)
        c.open[sid] = k
        b = blocks[r]
        c.out += len(b).to_bytes(3, "big") + hdr_flags + sid.to_bytes(4, "big")
        c.out += b
        t_sub.append(clock())

    def end(c: Conn, sid: int, t: int, bit: int = 0) -> None:
        k = c.open.pop(sid, None)
        if k is None:
            return
        body = c.body.pop(sid, b"")
        if not bit and body != pages[rank[k]]:
            bit = BODY_WRONG
        t_done[k] = t
        flags[k] |= bit
        if not state["stop"]:
            if t >= state["until"]:
                state["stop"] = True
            else:
                start(c)

    def on_headers(c: Conn, sid: int, fl: int, payload: bytes, t: int):
        k = c.open.get(sid)
        if k is None:
            return
        status, who, xff = None, None, None
        for name, value in W.decode(W.header_block(fl, payload)):
            if name == b":status":
                status = value
            elif name == b"x-backend":
                who = value
            elif name == b"x-seen-forwarded-for":
                xff = value
        if who is not None:
            ident[k] = idents.get(who, UNKNOWN_IDENT)
        flags[k] |= (0 if status == b"200" else NOT_200) | \
            (0 if xff == c.src else XFF_WRONG)
        if fl & W.F_END_STREAM:
            end(c, sid, t)

    def read(c: Conn) -> None:
        try:
            data = c.sock.recv(RECV)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            data = b""
        t = clock()
        if not data:
            c.dead = True
            sel.unregister(c.sock)
            return
        c.buf += data
        for ftype, fl, sid, payload in W.frames(c.buf):
            if ftype == W.DATA:
                c.got += len(payload)
                if sid in c.open:
                    c.body[sid] = c.body.get(sid, b"") + payload
                if fl & W.F_END_STREAM:
                    end(c, sid, t)
            elif ftype == W.HEADERS:
                on_headers(c, sid, fl, payload, t)
            elif ftype == W.RST_STREAM:
                end(c, sid, t, RESET)
            elif ftype == W.SETTINGS and not fl & W.F_ACK:
                c.out += W.frame(W.SETTINGS, W.F_ACK, 0)
            elif ftype == W.PING and not fl & W.F_ACK:
                c.out += W.frame(W.PING, W.F_ACK, 0, payload)
            elif ftype == W.GOAWAY:
                c.dead = True
        if c.got >= TOP_UP:
            c.out += W.window_update(0, c.got)
            c.got = 0
        flush(c)

    def flush(c: Conn) -> None:
        while c.out and not c.dead:
            try:
                sent = c.sock.send(c.out)
            except (BlockingIOError, InterruptedError):
                # rare: the kernel buffer is full; wait for it to drain
                sel.modify(c.sock, selectors.EVENT_READ | selectors.EVENT_WRITE, c)
                return
            except OSError:
                c.dead = True
                return
            del c.out[:sent]
        if not c.dead and c.sock.fileno() >= 0:
            sel.modify(c.sock, selectors.EVENT_READ, c)

    try:
        for c in conns:
            for _ in range(streams):
                start(c)
            flush(c)
        end_at = None
        while True:
            if state["stop"] and end_at is None:
                end_at = clock() + int(drain_s * 1e9)
            if end_at is not None and (clock() >= end_at or not any(
                    c.open for c in conns if not c.dead)):
                break
            if all(c.dead for c in conns):
                break
            for key, ev in sel.select(0.05):
                c = key.data
                if c is None:
                    line = os.read(stdin, 4096).split()
                    if not line:
                        raise _Term()
                    if line[0] == b"until":
                        state["until"] = int(line[1])
                    continue
                if ev & selectors.EVENT_READ:
                    read(c)
                elif ev & selectors.EVENT_WRITE:
                    flush(c)
            if not state["stop"] and clock() >= state["until"]:
                state["stop"] = True
    except _Term:
        pass
    n = len(t_sub)
    return {"rank": rank[:n], "t_sub": t_sub, "t_done": t_done[:n],
            "ident": ident[:n], "flags": flags[:n]}


def write_results(path: str, rec: dict) -> None:
    """n int64, then rank int32[n], t_sub int64[n], t_done int64[n],
    ident int32[n], flags int8[n]."""
    with open(path, "wb") as f:
        f.write(struct.pack("<q", len(rec["t_sub"])))
        for key in ("rank", "t_sub", "t_done", "ident", "flags"):
            a = rec[key]
            if sys.byteorder != "little" and a.itemsize > 1:
                a = array(a.typecode, a)
                a.byteswap()
            a.tofile(f)


def read_results(path: str) -> dict:
    with open(path, "rb") as f:
        raw = f.read()
    (n,) = struct.unpack_from("<q", raw)
    out, off = {"n": n}, 8
    for key, tc in (("rank", "i"), ("t_sub", "q"), ("t_done", "q"),
                    ("ident", "i"), ("flags", "b")):
        a = array(tc)
        a.frombytes(raw[off:off + n * a.itemsize])
        if sys.byteorder != "little" and a.itemsize > 1:
            a.byteswap()
        out[key] = a
        off += n * a.itemsize
    return out


def connect(server: str, sources: list) -> list:
    """One connection from each source address, preface and SETTINGS
    sent: -> [(socket, source bytes)]."""
    host, port = server.rsplit(":", 1)
    out = []
    for src in sources:
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.bind((src, 0))
        s.connect((host, int(port)))
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.sendall(W.PREFACE + W.settings([(W.S_ENABLE_PUSH, 0),
                                          (W.S_INITIAL_WINDOW, STREAM_WINDOW)])
                  + W.window_update(0, W.MAX_WINDOW - 65535))
        s.setblocking(False)
        out.append((s, src.encode()))
    return out


def client(a) -> int:
    sources = a.sources.split(",")
    blocks, pages, rows, idents = load(a.pool, a.seq, a.idents,
                                       len(sources))
    conns = [Conn(s, src, rows[c])
             for c, (s, src) in enumerate(connect(a.server, sources))]
    signal.signal(signal.SIGTERM, _term)
    print("ready", flush=True)
    rec = closed_loop(conns, blocks, pages, idents, a.streams, a.drain)
    for c in conns:
        c.sock.close()
    write_results(a.out, rec)
    print(f"done {len(rec['t_sub'])}", flush=True)
    return 0


def ceiling(a) -> int:
    """The client's own ceiling: against the backends process alone (one
    listener, the same responses), in processes of their own,
    `--seconds` of the closed loop -> streams a second."""
    me = os.path.abspath(__file__)
    tmp = tempfile.mkdtemp(prefix="h2_ceiling_")
    pool, seqf, ids, out = (os.path.join(tmp, x)
                            for x in ("pool", "seq", "ids", "out"))
    n = 1024
    with open(pool, "w") as f:
        f.write("".join(f"www.svc{i}.ns{i % 997}.example.com\n"
                        for i in range(n)))
    with open(seqf, "wb") as f:
        array("i", [(i * 7) % n for i in range(a.connections << 14)]).tofile(f)
    back = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "h2_backends.py"), "serve",
         "--listen", "1"], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        text=True)
    cl = None
    try:
        port = int(back.stdout.readline().split()[1])
        with open(ids, "w") as f:
            f.write(f"b|127.0.0.1:{port}\n")
        cl = subprocess.Popen(
            [sys.executable, me, "client", "--server", f"127.0.0.1:{port}",
             "--sources", ",".join(f"127.1.0.{c + 1}"
                                   for c in range(a.connections)),
             "--streams", str(a.streams), "--pool", pool, "--seq", seqf,
             "--idents", ids, "--out", out, "--drain", "2"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        cl.stdout.readline()
        time.sleep(1.0)
        t0 = time.perf_counter_ns()
        t1 = t0 + int(a.seconds * 1e9)
        cl.stdin.write(f"until {t1}\n")
        cl.stdin.flush()
        cl.wait(a.seconds + 60)
        r = read_results(out)
    finally:
        for p in (cl, back):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait()
        shutil.rmtree(tmp, ignore_errors=True)
    done = sum(1 for t in r["t_done"] if t0 <= t < t1)
    print(f"client ceiling against the backends alone: "
          f"{done / a.seconds:.0f} streams/s at {a.connections} x "
          f"{a.streams} streams, {a.seconds:g} s", flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)
    c = sub.add_parser("client")
    c.add_argument("--server", required=True)
    c.add_argument("--sources", required=True)
    c.add_argument("--streams", type=int, required=True)
    c.add_argument("--pool", required=True)
    c.add_argument("--seq", required=True)
    c.add_argument("--idents", required=True)
    c.add_argument("--out", required=True)
    c.add_argument("--drain", type=float, default=10.0)
    g = sub.add_parser("ceiling")
    g.add_argument("--seconds", type=float, default=10.0)
    g.add_argument("--connections", type=int, default=16)
    g.add_argument("--streams", type=int, default=48)
    a = ap.parse_args(argv)
    return {"client": client, "ceiling": ceiling}[a.mode](a)


if __name__ == "__main__":
    sys.exit(main())
