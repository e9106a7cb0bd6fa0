"""The h2 hop encoder's field memo (processors/hpack.py StaticEncoder):
its blocks are byte for byte what the unmemoised 0-size-table encoder
emits — memo cold, warm and right after a clear — and decode through
both the program's and the benchmark's decoders; credentials, sensitive
and long fields are never stored; the memo keeps its bound and counts
what it does; and an h2 session writes the same bytes to both hops with
the memo cold, warm, or replaced by the plain encoder."""
import os
import random
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "drivers"))

import h2_wire as W  # noqa: E402  (stdlib HPACK, no program import)

from vproxy_tpu.processors import hpack  # noqa: E402
from vproxy_tpu.processors.base import ProcessorEngine  # noqa: E402
from vproxy_tpu.processors.h2 import (  # noqa: E402
    DATA, F_END_HEADERS, F_END_STREAM, HEADERS, PREFACE, SETTINGS,
    H2Session, frame)
from vproxy_tpu.utils.metrics import GlobalInspection  # noqa: E402

NAMES = [b":method", b":path", b":authority", b":status", b":scheme",
         b"content-type", b"content-length", b"accept-encoding",
         b"x-backend", b"x-forwarded-for", b"x-seen-forwarded-for",
         b"user-agent", b"x-custom", b"grpc-status", b"te"]
VALUES = [b"", b"GET", b"POST", b"/", b"/index.html", b"http", b"200",
          b"404", b"gzip, deflate", b"text/html", b"612",
          b"www.example.com", b"10.0.0.1, 127.1.0.7", b"trailers",
          b"~~~~{{}}||\\\\", b"\x00\x01\xfe\xff", "café".encode(),
          b"q" * 200, b"e" * 300]


def counters() -> tuple:
    gi = GlobalInspection.get()
    return (gi.get_counter("vproxy_h2_hpack_field_memo_total",
                           result="hit").value(),
            gi.get_counter("vproxy_h2_hpack_field_memo_total",
                           result="miss").value(),
            gi.get_counter("vproxy_h2_hpack_field_memo_clears_total")
            .value())


def random_headers(rng: random.Random) -> list:
    """A list mixing static full and name-only hits, new names, values
    Huffman shortens (lower case) and lengthens (rare symbols),
    non-ASCII bytes, empty and long values."""
    out = []
    for _ in range(rng.randint(0, 12)):
        if rng.random() < 0.25:
            out.append(rng.choice(hpack.STATIC_TABLE))
        else:
            name = rng.choice(NAMES + [b"x-r%d" % rng.randint(0, 50)])
            out.append((name, rng.choice(
                VALUES + [bytes(rng.randrange(256)
                                for _ in range(rng.randint(1, 40)))])))
    return out


def plain(headers, sensitive=None) -> bytes:
    return hpack.Encoder(max_table_size=0).encode(headers, sensitive)


@pytest.fixture
def cold():
    hpack._field_memo.clear()
    yield hpack._field_memo
    hpack._field_memo.clear()


@pytest.mark.parametrize("seed", [1, 7, 2**31 + 5])
def test_blocks_are_the_unmemoised_encoders_cold_warm_and_after_a_clear(
        cold, monkeypatch, seed):
    rng = random.Random(seed)
    lists = [random_headers(rng) for _ in range(60)]
    sens = [None if rng.random() < 0.7 else
            {rng.choice(NAMES), b":authority"} for _ in lists]
    enc = hpack.StaticEncoder()
    want = [plain(h, s) for h, s in zip(lists, sens)]
    assert [enc.encode(h, s) for h, s in zip(lists, sens)] == want  # cold
    assert cold
    assert [enc.encode(h, s) for h, s in zip(lists, sens)] == want  # warm
    # the same fields sensitive now, after they were stored plain
    assert enc.encode(lists[0], {n for n, _ in lists[0]}) == \
        plain(lists[0], {n for n, _ in lists[0]})
    # a bound small enough that the memo fills and clears inside the lists
    cold.clear()
    monkeypatch.setattr(hpack, "MEMO_FIELDS", 16)
    clears0 = counters()[2]
    assert [hpack.StaticEncoder().encode(h, s)
            for h, s in zip(lists, sens)] == want
    assert counters()[2] > clears0
    assert [enc.encode(h, s) for h, s in zip(lists, sens)] == want


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_blocks_decode_through_both_decoders(cold, seed):
    rng = random.Random(seed)
    enc = hpack.StaticEncoder()
    for _ in range(40):
        h = random_headers(rng)
        for _ in range(2):                  # cold, then warm
            block = enc.encode(h)
            assert hpack.Decoder().decode(block) == h
            assert W.decode(block) == h


def test_credentials_sensitive_and_long_fields_are_never_stored(cold):
    enc = hpack.StaticEncoder()
    creds = [(n, b"secret-%d" % i) for i, n in
             enumerate(sorted(hpack.NO_MEMO_NAMES))]
    longest = (b"x-long", b"v" * (hpack.MEMO_FIELD_MAX - 6))
    over = (b"x-long", b"v" * (hpack.MEMO_FIELD_MAX - 5))
    fields = creds + [longest, over, (b"x-token", b"t0"), (b":path", b"/a")]
    for _ in range(2):
        block = enc.encode(fields, sensitive={b"x-token"})
        assert block == plain(fields, {b"x-token"})
    assert set(cold) == {longest, (b":path", b"/a")}


def test_the_memo_keeps_its_bound_and_counts_its_clears(cold, monkeypatch):
    monkeypatch.setattr(hpack, "MEMO_FIELDS", 8)
    enc = hpack.StaticEncoder()
    clears0 = counters()[2]
    for i in range(100):
        enc.encode([(b"x-n", b"%d" % i)])
        assert len(cold) <= 8
    # the 9th, 17th, ... distinct field finds the memo full
    assert counters()[2] - clears0 == (100 - 1) // 8
    assert len(cold) == 100 - 8 * ((100 - 1) // 8)


def test_hits_and_misses_add_up_to_the_fields_encoded(cold):
    rng = random.Random(11)
    enc = hpack.StaticEncoder()
    hit0, miss0, _ = counters()
    lists = [random_headers(rng) for _ in range(30)]
    for h in lists + lists:
        enc.encode(h, {b"x-custom"})
    hit, miss, _ = counters()
    n = 2 * sum(len(h) for h in lists)
    assert (hit - hit0) + (miss - miss0) == n
    assert hit - hit0 >= sum(1 for h in lists for k, v in h
                             if k != b"x-custom" and len(k) + len(v) <= 256)


# ------------------------------------------------- one stream, both hops

class _Engine(ProcessorEngine):
    """What a session writes, recorded; the select answered inline."""

    def __init__(self):
        self.front = bytearray()
        self.back = bytearray()
        self.closed = False

    def send_front(self, data):
        self.front += data

    def send_back(self, conn_id, data):
        self.back += data

    def select_async(self, hint, cb):
        cb(SimpleNamespace(key=("10.9.0.1", 80)))

    def open(self, sel):
        return 1

    def close_back(self, conn_id):
        pass

    def close(self):
        self.closed = True


REQ = [(b":method", b"GET"), (b":scheme", b"http"), (b":path", b"/"),
       (b":authority", b"w17.groups.example"), (b"user-agent", b"h2load")]
RESP = [(b":status", b"200"), (b"content-type", b"text/html"),
        (b"content-length", b"5"), (b"x-backend", b"g12/m3"),
        (b"x-seen-forwarded-for", b"127.1.0.7")]


def relay_one_stream() -> tuple:
    """A request on stream 1 and its response relayed back through one
    H2Session: (bytes toward the client, bytes toward the backend)."""
    eng = _Engine()
    s = H2Session(eng, ("127.1.0.7", 40000))
    client = hpack.Encoder()
    s.on_front_data(PREFACE + frame(SETTINGS, 0, 0) + frame(
        HEADERS, F_END_HEADERS | F_END_STREAM, 1, client.encode(REQ)))
    backend = hpack.Encoder()
    s.on_back_data(1, frame(SETTINGS, 0, 0) + frame(
        HEADERS, F_END_HEADERS, 1, backend.encode(RESP)) + frame(
        DATA, F_END_STREAM, 1, b"hello"))
    assert s.streams_done == 1
    return bytes(eng.front), bytes(eng.back)


def test_a_relayed_stream_writes_the_same_bytes_cold_warm_and_unmemoised(
        cold, monkeypatch):
    first = relay_one_stream()             # the memo cold
    assert (b":authority", b"w17.groups.example") in cold
    assert relay_one_stream() == first     # warm
    monkeypatch.setattr(hpack.StaticEncoder, "encode", hpack.Encoder.encode)
    assert relay_one_stream() == first     # the plain encoder's bytes
    front, back = first
    assert back.startswith(PREFACE)
    for raw, want in ((back[len(PREFACE):],
                       REQ + [(b"x-forwarded-for", b"127.1.0.7")]),
                      (front, RESP)):
        blocks = [p for t, _f, _s, p in W.frames(bytearray(raw))
                  if t == HEADERS]
        assert [hpack.Decoder().decode(b) for b in blocks] == [want]
