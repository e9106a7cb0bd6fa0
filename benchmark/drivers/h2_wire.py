"""HTTP/2 framing and HPACK for the h2 cell's own processes.

Plain Python, no import of the program: the client (`h2_client.py`),
the backends (`h2_backends.py`) and the cell's control speak HTTP/2
through this file alone, so a fault of the program's h2 processor or of
its HPACK cannot cancel out against the same fault on the other end.

What it does: frames (RFC 7540 §4.1), a header-block decoder (RFC 7541:
the static table, Huffman-coded strings and every literal form; the
dynamic table is not kept, and a block that indexes it raises — the
program's hop encoder never adds to it) and an encoder of literals
without indexing, Huffman never used. Decoded blocks are memoised by
their bytes: a static-only peer sends the same bytes for the same
headers.
"""
from __future__ import annotations

import struct

PREFACE = b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"
DATA, HEADERS, PRIORITY, RST_STREAM, SETTINGS, PUSH_PROMISE, PING, GOAWAY, \
    WINDOW_UPDATE, CONTINUATION = range(10)
F_END_STREAM = 0x1
F_ACK = 0x1
F_END_HEADERS = 0x4
F_PADDED = 0x8
F_PRIORITY = 0x20
S_ENABLE_PUSH, S_MAX_CONCURRENT, S_INITIAL_WINDOW = 2, 3, 4
MAX_WINDOW = (1 << 31) - 1


# The response body: nginx's stock index.html is 612 bytes. A backend
# writes the request's :authority in front of it, so the client can
# tell its stream's body from a truncated one or another request's.
PAGE = (b"<!DOCTYPE html>\n<html>\n<head>\n<title>Welcome to nginx!</title>"
        b"\n").ljust(612, b" ")


def page(authority: bytes) -> bytes:
    """The 612-byte body a backend answers a request for `authority`."""
    return (authority + b"\n" + PAGE)[:len(PAGE)]


class H2WireError(Exception):
    pass


def frame(ftype: int, flags: int, sid: int, payload: bytes = b"") -> bytes:
    return len(payload).to_bytes(3, "big") + bytes((ftype, flags)) + \
        (sid & 0x7FFFFFFF).to_bytes(4, "big") + payload


def settings(pairs) -> bytes:
    return frame(SETTINGS, 0, 0, b"".join(struct.pack(">HI", k, v)
                                          for k, v in pairs))


def window_update(sid: int, inc: int) -> bytes:
    return frame(WINDOW_UPDATE, 0, sid, struct.pack(">I", inc))


def frames(buf: bytearray):
    """Whole frames at the head of `buf`, removed from it -> [(type,
    flags, sid, payload)]."""
    out, pos, n = [], 0, len(buf)
    while n - pos >= 9:
        ln = (buf[pos] << 16) | (buf[pos + 1] << 8) | buf[pos + 2]
        end = pos + 9 + ln
        if end > n:
            break
        sid = int.from_bytes(buf[pos + 5:pos + 9], "big") & 0x7FFFFFFF
        out.append((buf[pos + 3], buf[pos + 4], sid, bytes(buf[pos + 9:end])))
        pos = end
    if pos:
        del buf[:pos]
    return out


def header_block(flags: int, payload: bytes) -> bytes:
    """A HEADERS payload without its padding and priority fields."""
    pos, pad = 0, 0
    if flags & F_PADDED:
        pad, pos = payload[0], 1
    if flags & F_PRIORITY:
        pos += 5
    return payload[pos:len(payload) - pad]


# ------------------------------------------------------------------ HPACK

STATIC = [
    (":authority", ""), (":method", "GET"), (":method", "POST"),
    (":path", "/"), (":path", "/index.html"), (":scheme", "http"),
    (":scheme", "https"), (":status", "200"), (":status", "204"),
    (":status", "206"), (":status", "304"), (":status", "400"),
    (":status", "404"), (":status", "500"), ("accept-charset", ""),
    ("accept-encoding", "gzip, deflate"), ("accept-language", ""),
    ("accept-ranges", ""), ("accept", ""),
    ("access-control-allow-origin", ""), ("age", ""), ("allow", ""),
    ("authorization", ""), ("cache-control", ""),
    ("content-disposition", ""), ("content-encoding", ""),
    ("content-language", ""), ("content-length", ""),
    ("content-location", ""), ("content-range", ""), ("content-type", ""),
    ("cookie", ""), ("date", ""), ("etag", ""), ("expect", ""),
    ("expires", ""), ("from", ""), ("host", ""), ("if-match", ""),
    ("if-modified-since", ""), ("if-none-match", ""), ("if-range", ""),
    ("if-unmodified-since", ""), ("last-modified", ""), ("link", ""),
    ("location", ""), ("max-forwards", ""), ("proxy-authenticate", ""),
    ("proxy-authorization", ""), ("range", ""), ("referer", ""),
    ("refresh", ""), ("retry-after", ""), ("server", ""),
    ("set-cookie", ""), ("strict-transport-security", ""),
    ("transfer-encoding", ""), ("user-agent", ""), ("vary", ""),
    ("via", ""), ("www-authenticate", ""),
]      # RFC 7541 Appendix A, indices 1..61
STATIC_B = [(n.encode(), v.encode()) for n, v in STATIC]
STATIC_NAME = {}
for _i, (_n, _v) in reversed(list(enumerate(STATIC_B))):
    STATIC_NAME[_n] = _i + 1

# RFC 7541 Appendix B is a canonical Huffman code: the code lengths of
# the symbols 0..256 (EOS) fix every code. chr(48 + length) a symbol
_LENGTHS = (
    "=GLLLLLLLHNLLNLLLLLLLLNLLLLLLLLL6::<=68;::8;8666555666666678?6<:=67777"
    "777777777777777777878=C=>6?56565666577666567655677777?;>=LDFDDFFFGFGGG"
    "GGHGHHFGHGGGGEFGFGGHFEDFFGGEGFFHEFGGEEFEGFGGDFFFGFFGJJDCFGFIJJJKKJHICE"
    "JKKJKHEEJJLKKKDHDEFEEGFFIIHHJGJKJJKKKKKLKKKKKJN")


def _canonical():
    """-> (lengths in use, limit[l], first[l], symbols by code)."""
    lens = [ord(c) - 48 for c in _LENGTHS]
    order = sorted(range(257), key=lambda s: (lens[s], s))
    first, limit, base = {}, {}, {}
    code, prev = 0, lens[order[0]]
    for n, s in enumerate(order):
        if n:
            code = (code + 1) << (lens[s] - prev)
        prev = lens[s]
        if lens[s] not in first:
            first[lens[s]], base[lens[s]] = code, n
        limit[lens[s]] = code + 1
    used = sorted(first)
    return used, limit, {l: (first[l], base[l]) for l in used}, order


_USED, _LIMIT, _FIRST, _ORDER = _canonical()


def huffman_decode(raw: bytes) -> bytes:
    total = 8 * len(raw)
    val = int.from_bytes(raw, "big")
    out = bytearray()
    pos = 0
    used, limit, first, order = _USED, _LIMIT, _FIRST, _ORDER
    while total - pos >= 5:
        for ln in used:
            if pos + ln > total:
                ln = 0
                break
            c = (val >> (total - pos - ln)) & ((1 << ln) - 1)
            if c < limit[ln]:
                break
        if ln == 0:
            break       # the padding: the EOS prefix, under 8 bits
        f, b = first[ln]
        sym = order[b + c - f]
        if sym == 256:
            raise H2WireError("EOS in a Huffman string")
        out.append(sym)
        pos += ln
    if total - pos >= 8:
        raise H2WireError("Huffman padding longer than 7 bits")
    return bytes(out)


def _int(data: bytes, pos: int, bits: int) -> tuple:
    mask = (1 << bits) - 1
    v = data[pos] & mask
    pos += 1
    if v < mask:
        return v, pos
    shift = 0
    while True:
        b = data[pos]
        pos += 1
        v += (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return v, pos


def _string(data: bytes, pos: int) -> tuple:
    huff = data[pos] & 0x80
    ln, pos = _int(data, pos, 7)
    raw = data[pos:pos + ln]
    if len(raw) != ln:
        raise H2WireError("truncated header string")
    return (huffman_decode(raw) if huff else bytes(raw)), pos + ln


def _static(idx: int) -> tuple:
    if not 1 <= idx <= len(STATIC_B):
        raise H2WireError(f"header index {idx} is not in the static table")
    return STATIC_B[idx - 1]


_decoded: dict = {}


def decode(block: bytes) -> list:
    """A header block -> [(name, value)] as bytes, memoised."""
    got = _decoded.get(block)
    if got is not None:
        return got
    out, pos, n = [], 0, len(block)
    try:
        while pos < n:
            b = block[pos]
            if b & 0x80:
                idx, pos = _int(block, pos, 7)
                out.append(_static(idx))
                continue
            if b & 0x40:
                idx, pos = _int(block, pos, 6)
            elif b & 0x20:
                _size, pos = _int(block, pos, 5)    # table size update
                continue
            else:
                idx, pos = _int(block, pos, 4)
            if idx:
                name = _static(idx)[0]
            else:
                name, pos = _string(block, pos)
            value, pos = _string(block, pos)
            out.append((name, value))
    except IndexError:
        raise H2WireError("truncated header block") from None
    if len(_decoded) < 1 << 17:
        _decoded[block] = out
    return out


def _enc_int(v: int, bits: int, first: int) -> bytes:
    mask = (1 << bits) - 1
    if v < mask:
        return bytes((first | v,))
    out = bytearray((first | mask,))
    v -= mask
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def encode(headers) -> bytes:
    """[(name, value)] as bytes -> a block: an exact static entry
    indexed, every other field a literal without indexing (its name
    indexed where the static table has it), no Huffman."""
    out = bytearray()
    for name, value in headers:
        try:
            out += _enc_int(STATIC_B.index((name, value)) + 1, 7, 0x80)
            continue
        except ValueError:
            pass
        idx = STATIC_NAME.get(name, 0)
        out += _enc_int(idx, 4, 0)
        if not idx:
            out += _enc_int(len(name), 7, 0) + name
        out += _enc_int(len(value), 7, 0) + value
    return bytes(out)
