"""Hash-based classify kernels — the O(1)-per-query fast path.

The dense matchers (ops/matchers.py) reproduce the reference's linear
scans as matmuls: exact, but O(rules) FLOPs per query — a 100k-rule
table costs ~1 TFLOP per 4k batch, far past the 10M matches/s target.
These kernels replace the scan with cuckoo-hash probes + tiny gather
verification, so per-query work is O(labels + uri-lengths) regardless
of table size. Semantics stay bit-for-bit the reference's:

* hint match (Upstream.searchForGroup, Upstream.java:187-198; scoring
  Hint.matchLevel, Hint.java:92-160): a winning rule must have an
  exact/suffix/wildcard host match or an exact/prefix/wildcard uri
  match, so the candidate set is exactly
    - the host-table bucket for the query host (exact) and for each
      dot-suffix of it (suffix rules),
    - the uri-table bucket for each query-uri prefix whose length some
      rule uri has,
    - the (small) lists of host="*" / uri="*" rules.
  Each candidate is then scored with the full matchLevel formula from
  its gathered rule record — byte compares, no trust in hashes —
  and reduced with (max level, then min rule index).
* cidr first-match (RouteTable.lookup RouteTable.java:44,
  SecurityGroup.allow SecurityGroup.java:30-45): rules expand to the
  same <=3 (value,mask,family) patterns as the dense compiler; patterns
  group by (family, mask16) and each group gets a cuckoo table keyed on
  masked address bytes. Any rule matching a query is discoverable via
  its group's probe, so min-rule-index over all probe hits equals the
  ordered linear scan exactly (incl. ACL port-range buckets).
  A slot carries its bucket: row s of `b_rows` holds the rules whose
  key sits in slot s, ascending — K rule indices and, for an ACL, their
  K range starts and K range ends — so after the key verify one row
  gather a (query, group) is the whole candidate walk, and the port
  gate and the least-index reduce run on that row. K is the table's
  fattest bucket rounded to a power of two (1 for a route table, whose
  keys are distinct), at most BUCKET_INLINE; a longer bucket continues
  in overflow rows appended to `b_rows` and named by `b_next`, and a
  lookup makes ceil(fattest bucket / K) hops — a static number read
  from the table's shape (`b_shape`), 1 unless some network carries
  more than BUCKET_INLINE port ranges. Memory is slots x K + the long
  buckets' own entries, never slots x the fattest bucket.

Query-side hashing is host-side numpy (rolling FNV-64: one pass gives
every dot-suffix / uri-prefix hash); the LPM kernel hashes masked
addresses on-device with FNV-32 (u32 wraparound matches numpy).
"""
from __future__ import annotations

import math
import os
from dataclasses import dataclass, field
from functools import lru_cache
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..rules.ir import AclRule, HintRule
from . import cuckoo as CK
from .tables import MAX_HOST, MAX_URI, V4, V6, _pad_cap

HOST_SHIFT = 10
URI_MAX_SCORE = 1023
DOT = ord(".")

# probe-count tiers for host dot-suffixes: static shapes, encoder picks
# the smallest tier covering the batch (jit caches one program per tier).
# Every padded probe is one wasted ~23ns row gather per query (measured
# r4), so the low tiers are fine-grained: typical 3-5-label domains land
# on 5/7 instead of 9
MAXP_TIERS = (5, 7, 9, 17, 33, 66)


def _pow2(n: int, lo: int = 2) -> int:
    c = lo
    while c < n:
        c <<= 1
    return c


# --------------------------------------------------------------- hint side


@dataclass
class HashHintTable:
    """Compiled hash-path hint table: device arrays + host-side meta the
    encoder needs (salts, caps, the rule-uri length set).

    `hw`/`uw` are the host/uri byte-compare windows — sized to the
    table's longest key (rounded up), not the global MAX_HOST/MAX_URI,
    because the query payload is h2d-bandwidth that bounds classify
    throughput: bytes beyond the longest rule key can never influence a
    match (exact needs equal lengths, suffix/prefix compare only rule
    bytes), so they are never shipped."""

    n: int
    r_cap: int
    arrays: dict  # numpy arrays; engine device_puts them
    host_cap: int
    host_salts: tuple
    uri_cap: int
    uri_salts: tuple
    lset: list  # distinct rule-uri lengths (normal rules)
    hw: int  # host window: max rule-host len + 1 boundary byte (padded)
    uw: int  # uri window: max rule-uri len (padded)
    caps: dict = field(default_factory=dict)  # all static caps for reuse


def _prune_list(rules, items, sig):
    seen, keep = set(), []
    for i in sorted(items):
        s = sig(rules[i])
        if s not in seen:
            seen.add(s)
            keep.append(i)
    return keep


def compile_hint_hash(rules: Sequence[HintRule],
                      caps: Optional[dict] = None) -> HashHintTable:
    caps = dict(caps or {})
    n = len(rules)
    r_cap = caps.get("r_cap") or _pad_cap(n, 256)
    if n > r_cap:
        r_cap = _pad_cap(n, 256)
    # past _PACK_I32_MAX rules the kernel's (level, index) reduction
    # switches from i32 packing to the two-pass form (see
    # hint_hash_match) — no capacity assert needed anymore

    host_buckets: dict[bytes, list[int]] = {}
    uri_buckets: dict[bytes, list[int]] = {}
    wh: list[int] = []
    wu: list[int] = []
    max_hl = max_ul = 0
    for i, r in enumerate(rules):
        if not (i & 31):
            CK.coop_yield()
        if r.is_empty():
            continue
        if r.host is not None:
            if len(r.host.encode()) > MAX_HOST:
                raise ValueError(f"host rule longer than {MAX_HOST}: {r.host!r}")
            max_hl = max(max_hl, len(r.host.encode()))
        if r.uri is not None:
            if len(r.uri.encode()) > MAX_URI:
                raise ValueError(f"uri rule longer than {MAX_URI}: {r.uri!r}")
            max_ul = max(max_ul, len(r.uri.encode()))
    # compare windows: +1 host byte for the suffix boundary dot
    hw = min(MAX_HOST + 1, max(caps.get("hw", 0), _pow2(max_hl + 1, 8)))
    uw = min(MAX_URI, max(caps.get("uw", 0), _pow2(max(max_ul, 1), 8)))

    r_active = np.zeros(r_cap, bool)
    r_port = np.zeros(r_cap, np.int32)
    r_host_kind = np.zeros(r_cap, np.int32)  # 0 none / 1 normal / 2 wild
    r_host_len = np.zeros(r_cap, np.int32)
    r_host = np.zeros((r_cap, hw), np.uint8)  # reversed bytes
    r_uri_kind = np.zeros(r_cap, np.int32)
    r_uri_len = np.zeros(r_cap, np.int32)
    r_uri = np.zeros((r_cap, uw), np.uint8)
    r_uri_score = np.zeros(r_cap, np.int32)

    for i, r in enumerate(rules):
        if not (i & 31):
            CK.coop_yield()
        if r.is_empty():
            continue
        r_active[i] = True
        r_port[i] = r.port
        if r.host is not None:
            hb = r.host.encode()[::-1]
            r_host_kind[i] = 2 if r.host == "*" else 1
            r_host_len[i] = len(hb)
            r_host[i, : len(hb)] = np.frombuffer(hb, np.uint8)
            host_buckets.setdefault(bytes(hb), []).append(i)
            if r.host == "*":
                wh.append(i)
        if r.uri is not None:
            ub = r.uri.encode()
            r_uri_kind[i] = 2 if r.uri == "*" else 1
            r_uri_len[i] = len(ub)
            r_uri[i, : len(ub)] = np.frombuffer(ub, np.uint8)
            r_uri_score[i] = min(len(ub) + 1, URI_MAX_SCORE)
            uri_buckets.setdefault(bytes(ub), []).append(i)
            if r.uri == "*":
                wu.append(i)

    # Bucket pruning (exactness-preserving): members of one bucket share
    # the keyed attribute, so a later member whose OTHER attributes equal
    # an earlier member's can never outscore it (same level, later index)
    # — keep only the earliest per residual signature. For uri buckets
    # the residual is just the port: a member whose host matches a query
    # surfaces via the (complete) host bucket with a >= level, so among
    # pure-uri contributions, earliest-per-port dominates. This is what
    # keeps candidate counts O(1) when thousands of rules share one uri.
    for bi, k in enumerate(host_buckets):
        if not (bi & 63):
            CK.coop_yield()
        host_buckets[k] = _prune_list(rules, host_buckets[k],
                                      lambda r: (r.uri, r.port))
    for bi, k in enumerate(uri_buckets):
        if not (bi & 63):
            CK.coop_yield()
        uri_buckets[k] = _prune_list(rules, uri_buckets[k], lambda r: r.port)
    # wh (host="*") members differ in uri, which the wildcard path must
    # itself score -> dedupe per (uri, port). wu (uri="*") members' host
    # relation is covered by the complete host buckets whenever it fires,
    # so the global list only represents the host-miss (0|1) case ->
    # earliest per port suffices even with thousands of wu rules.
    wh = _prune_list(rules, wh, lambda r: (r.uri, r.port))
    wu = _prune_list(rules, wu, lambda r: r.port)

    ht, hb_items = CK.build_cuckoo(host_buckets, hw,
                                   cap=caps.get("host_cap"), salt_base=1)
    ut, ub_items = CK.build_cuckoo(uri_buckets, uw,
                                   cap=caps.get("uri_cap"), salt_base=2)
    bh = max(caps.get("bh", 0), _pow2(int(ht.bucket_count.max(initial=1))))
    bu = max(caps.get("bu", 0), _pow2(int(ut.bucket_count.max(initial=1))))
    whc = max(caps.get("wh", 0), _pow2(len(wh), 2))
    wuc = max(caps.get("wu", 0), _pow2(len(wu), 2))
    hbc = max(caps.get("hb_items", 0), _pow2(max(len(hb_items), 1), 256))
    ubc = max(caps.get("ub_items", 0), _pow2(max(len(ub_items), 1), 256))

    lset = sorted({int(l) for l, k in zip(r_uri_len, r_uri_kind) if k == 1})
    lset_cap = max(caps.get("lset", 0), _pow2(max(len(lset), 1), 4))
    if len(lset) > lset_cap:
        lset_cap = _pow2(len(lset), 4)

    def pad_items(items, cap):
        out = np.full(cap, -1, np.int32)
        out[: len(items)] = items
        return out

    arrays = {
        "r_active": r_active, "r_port": r_port,
        "r_host_kind": r_host_kind, "r_host_len": r_host_len, "r_host": r_host,
        "r_uri_kind": r_uri_kind, "r_uri_len": r_uri_len, "r_uri": r_uri,
        "r_uri_score": r_uri_score,
        "hk_used": ht.used, "hk_len": ht.key_len, "hk_bytes": ht.key_bytes,
        "hk_bs": ht.bucket_start, "hk_bc": np.minimum(ht.bucket_count, bh),
        "hb_items": pad_items(hb_items, hbc),
        "uk_used": ut.used, "uk_len": ut.key_len, "uk_bytes": ut.key_bytes,
        "uk_bs": ut.bucket_start, "uk_bc": np.minimum(ut.bucket_count, bu),
        "ub_items": pad_items(ub_items, ubc),
        "wh_idx": pad_items(wh, whc), "wu_idx": pad_items(wu, wuc),
        # bucket caps as array shapes: [bh]/[bu] dummy arange carries the
        # static bucket width into the jitted kernel
        "bh_iota": np.arange(bh, dtype=np.int32),
        "bu_iota": np.arange(bu, dtype=np.int32),
    }
    return HashHintTable(
        n=n, r_cap=r_cap, arrays=arrays,
        host_cap=ht.cap, host_salts=(ht.salt1, ht.salt2),
        uri_cap=ut.cap, uri_salts=(ut.salt1, ut.salt2), lset=lset,
        hw=hw, uw=uw,
        caps={"r_cap": r_cap, "host_cap": ht.cap, "uri_cap": ut.cap,
              "bh": bh, "bu": bu, "wh": whc, "wu": wuc, "hw": hw, "uw": uw,
              "hb_items": hbc, "ub_items": ubc, "lset": lset_cap})


# ------------------------------------------------------ the query arena
#
# A launch is handed ONE numpy array: the implicit upload of a jitted
# call costs the calling thread 60-90 us an argument and hands the GIL
# back once an argument, whatever the bytes (PERF.md §7), so a batch's
# query columns are views of one int32 buffer — its arena — and the
# served programs' first lines slice the buffer back into the columns
# their bodies read (unpack_arena). The layout is a pure function of the
# shapes that key the compile cache anyway (the bucket, the compare
# windows, the probe tier, the table's uri-length cap; ports and a
# table-id column for cidr): it adds no program shape.


class ArenaLayout(NamedTuple):
    """Where each column of a batch lies in its arena. Hashable: the
    served programs take it as a static argument."""
    words: int      # the arena's length, int32 words
    fields: tuple   # (name, word offset, dtype name, shape), in order


def arena_layout(cols: Sequence[tuple]) -> ArenaLayout:
    """cols: (name, dtype name, shape) a column, in the order they lie.
    Every column starts on a word: an int32 column takes its elements,
    a one-byte column (uint8, bool) its bytes rounded up to whole words,
    so each view is aligned whatever the order."""
    fields, off = [], 0
    for name, dt, shape in cols:
        fields.append((name, off, dt, shape))
        n = math.prod(shape)
        off += n if dt == "int32" else -(-n // 4)
    return ArenaLayout(off, tuple(fields))


def _widest_tier(hw: int) -> int:
    """The probe tier that covers any row of an hw-byte host window."""
    return next((t for t in MAXP_TIERS if t >= hw), MAXP_TIERS[-1])


@lru_cache(maxsize=None)     # a layout a program shape: few
def hint_layout(cap: int, hw: int, uw: int, maxp: int, lw: int,
                ns: int = 2, slots: bool = False) -> ArenaLayout:
    """A hint batch's arena at bucket `cap`: compare windows hw / uw,
    probe tier maxp, uri-length cap lw, slot blocks under ns salts (a
    table's two; S shards' 2 S), and the Maglev slot column of the two
    fused programs. The host-probe block lies last: its tier is known
    only when the batch has been walked, and everything before it lies
    where it does at every tier."""
    cols = [("hlen", "int32", (cap,)), ("ulen", "int32", (cap,)),
            ("port", "int32", (cap,)), ("up_len", "int32", (cap, lw)),
            ("up_slots", "int32", (ns, cap, lw))]
    if slots:
        cols.append(("slots", "int32", (cap,)))
    cols += [("has_host", "bool", (cap,)), ("has_uri", "bool", (cap,)),
             ("hostb", "uint8", (cap, hw)), ("urib", "uint8", (cap, uw)),
             ("hp_len", "int32", (cap, maxp)),
             ("hp_slots", "int32", (ns, cap, maxp))]
    return arena_layout(cols)


@lru_cache(maxsize=None)
def cidr_layout(cap: int, gated: bool = False,
                tid: bool = False) -> ArenaLayout:
    """A cidr batch's arena at bucket `cap`: family and address, the
    port column where the table compares ports (an ACL), the table-id
    column where the lookups name their tables (a table set)."""
    cols = [("fam", "int32", (cap,))]
    if gated:
        cols.append(("port", "int32", (cap,)))
    if tid:
        cols.append(("tid", "int32", (cap,)))
    cols.append(("a16", "uint8", (cap, 16)))
    return arena_layout(cols)


def arena_views(arena: np.ndarray, fields: Sequence[tuple]) -> dict:
    """The columns `fields` (a layout's, or some of them) of `arena` as
    numpy views, by name."""
    out = {}
    for name, off, dt, shape in fields:
        n = math.prod(shape)
        if dt == "int32":
            out[name] = arena[off: off + n].reshape(shape)
        else:
            out[name] = arena[off: off + -(-n // 4)].view(np.uint8)[:n] \
                .view(dt).reshape(shape)
    return out


def unpack_arena(buf: jnp.ndarray, layout: ArenaLayout) -> dict:
    """arena_views on the device: the first lines of a served program.
    Static slices of the one int32 buffer; a byte column comes out of
    its words by bitcast (the low byte first: the host's order, which
    chip_smoke.py holds on the chip)."""
    out = {}
    with jax.named_scope("unpack"):
        for name, off, dt, shape in layout.fields:
            n = math.prod(shape)
            if dt == "int32":
                out[name] = buf[off: off + n].reshape(shape)
                continue
            by = jax.lax.bitcast_convert_type(
                buf[off: off + -(-n // 4)], jnp.uint8)
            by = by.reshape(-1)[:n].reshape(shape)
            out[name] = by != 0 if dt == "bool" else by
    return out


class QueryArena(dict):
    """One encoded batch: the dict of query columns that every backend
    and the plain kernels take, each a numpy view of `arena` — the one
    buffer a served launch is handed, with `layout` — and `slots`, the
    Maglev slot column where the batch has one. Fresh every batch: the
    runtime may alias host memory (the CPU backend does), so an arena
    is never written again once a launch has taken it."""

    __slots__ = ("arena", "layout", "slots")

    def __init__(self, cols: dict, arena: np.ndarray, layout: ArenaLayout,
                 slots: Optional[np.ndarray] = None):
        super().__init__(cols)
        self.arena, self.layout, self.slots = arena, layout, slots


# to jax a QueryArena is the dict it holds (the plain kernels are traced
# and jitted on it as on any dict of columns)
jax.tree_util.register_pytree_node(
    QueryArena,
    lambda q: (tuple(q[k] for k in sorted(q)), tuple(sorted(q))),
    lambda keys, cols: dict(zip(keys, cols)))


# the columns of a hint batch that every salt shares
_HINT_COLS = ("hostb", "hlen", "has_host", "urib", "ulen", "has_uri", "port",
              "hp_len", "up_len")


class _HintArena:
    """A hint batch's arena while it is encoded: allocated once at the
    widest probe tier — the tier is known only after the walk — with
    every column before the host-probe block in place (windows, lengths
    and flags zero, uri probes -1: a pad row is the arena's fill), then
    cut to the batch's tier by probes()."""

    def __init__(self, cap: int, hw: int, uw: int, lw: int, ns: int = 2,
                 slots: bool = False):
        self._shape, self._tail = (cap, hw, uw), (lw, ns, slots)
        widest = hint_layout(cap, hw, uw, _widest_tier(hw), *self._tail)
        self.buf = np.empty(widest.words, np.int32)
        self.buf[: widest.fields[-2][1]] = 0    # up to the probe block
        self.cols = arena_views(self.buf, widest.fields[:-2])
        self.cols["up_len"].fill(-1)
        self.cols["up_slots"].fill(-1)

    def probes(self, maxp: int) -> tuple:
        """Cut the arena to tier maxp -> (hp_len [cap, maxp], hp_slots
        [ns, cap, maxp]), -1 filled."""
        self.layout = hint_layout(*self._shape, maxp, *self._tail)
        self.buf = self.buf[: self.layout.words]
        self.buf[self.layout.fields[-2][1]:] = -1
        self.cols.update(arena_views(self.buf, self.layout.fields[-2:]))
        return self.cols["hp_len"], self.cols["hp_slots"]

    def queries(self) -> QueryArena:
        """The encoded batch (after probes()): a table's two salts' slot
        blocks under the names the kernels read."""
        c = self.cols
        q = {k: c[k] for k in _HINT_COLS}
        q["hp_slot1"], q["hp_slot2"] = c["hp_slots"]
        q["up_slot1"], q["up_slot2"] = c["up_slots"]
        return QueryArena(q, self.buf, self.layout, c.get("slots"))


def unpack_hint_arena(buf: jnp.ndarray, layout: ArenaLayout) -> dict:
    """unpack_arena for a hint batch -> (the query dict the hint bodies
    read, the Maglev slot column or None)."""
    q = unpack_arena(buf, layout)
    q["hp_slot1"], q["hp_slot2"] = q.pop("hp_slots")
    q["up_slot1"], q["up_slot2"] = q.pop("up_slots")
    return q, q.pop("slots", None)


def cidr_queries(cap: int, gated: bool = False,
                 tid: bool = False) -> QueryArena:
    """An empty cidr batch at bucket `cap` for the encoder to fill: every
    row a pad row (family -1: it matches no group; the rest zero)."""
    layout = cidr_layout(cap, gated, tid)
    arena = np.zeros(layout.words, np.int32)
    q = QueryArena(arena_views(arena, layout.fields), arena, layout)
    q["fam"].fill(-1)
    return q


def _scatter_strings(strs: list, win: np.ndarray, qlen: np.ndarray,
                     has: np.ndarray, reverse: bool) -> None:
    """One column of a batch (hosts or uris, None = absent) into its
    byte window: row i of `win` gets string i's first win.shape[1]
    utf-8 bytes — of the reversed string where `reverse` — and
    qlen / has its length and presence. Each string is encoded once and
    the batch's bytes are joined into one blob; only the bytes that
    exist are written, each at (its row, its column), so the cost is
    the batch's bytes and not rows x window."""
    n, w = len(strs), win.shape[1]
    enc = [b"" if s is None else s.encode() for s in strs]
    ln = np.fromiter(map(len, enc), np.int64, n)
    has[:n] = [s is not None for s in strs]
    qlen[:n] = np.minimum(ln, 1 << 20)
    blob = np.frombuffer(b"".join(enc), np.uint8)
    if not blob.size:
        return
    end = np.cumsum(ln)
    at = np.arange(blob.size)
    row = np.arange(0, n * w, w)  # each row's first cell in the flat window
    # cell of each blob byte: its row's first + its offset in its string,
    # counted from the string's last byte where the window holds the
    # reversed string
    flat = (np.repeat(row + (end - 1), ln) - at) if reverse \
        else (np.repeat(row - (end - ln), ln) + at)
    if int(ln.max()) > w:  # bytes past the window are dropped
        keep = flat - np.repeat(row, ln) < w
        flat, blob = flat[keep], blob[keep]
    win.reshape(-1)[flat] = blob


def _fill_query_windows(hints: Sequence, c: dict) -> None:
    """Shared query-byte-window fill for the vectorized encoders, into
    the arena's columns `c` (zero so far): hostb [cap,hw] u8 reversed,
    hlen, has_host, urib [cap,uw] u8, ulen, has_uri, port. Rows past
    len(hints) stay zero (pad rows).
    The batch is taken apart into its three columns once; nothing
    walks it hint by hint after that. The small-batch encoder fuses its
    walk with its per-hint hashing and intentionally does not share
    this."""
    _scatter_strings([h.host for h in hints], c["hostb"], c["hlen"],
                     c["has_host"], reverse=True)
    _scatter_strings([h.uri for h in hints], c["urib"], c["ulen"],
                     c["has_uri"], reverse=False)
    c["port"][:len(hints)] = [h.port for h in hints]


def _encode_hint_arrays(hints: Sequence, cap: int, hw: int, uw: int,
                        host_salts: Sequence[int], host_cap: int,
                        uri_salts: Sequence[int], uri_cap: int,
                        lset: Sequence[int], lset_w: int,
                        slots: bool = False) -> "_HintArena":
    """The vectorized encoders' one body -> the batch's arena, filled:
    the byte windows and the probe positions (shared by every salt),
    and the probe slots under each of `host_salts` / `uri_salts` — a
    table's two, or S shards' 2 S — as its columns hp_slots [NS, cap,
    P] / up_slots [NS, cap, lset_w]. slots: leave room for the Maglev
    slot column.

    Columns come out at `cap` rows; rows past len(hints) are pad rows
    (zero windows, -1 probes) that no hash pass ever saw."""
    b = len(hints)
    arena = _HintArena(cap, hw, uw, lset_w, len(host_salts), slots)
    c = arena.cols
    _fill_query_windows(hints, c)
    q_hostb, q_hlen, q_has_host = c["hostb"], c["hlen"], c["has_host"]
    q_urib, q_ulen, q_has_uri = c["urib"], c["ulen"], c["has_uri"]

    # --- host probes: every dot position p (suffix), then exact
    # (p = hlen), ascending. Valid probe lengths are <= hw-1 (no rule
    # host is longer; a boundary dot may sit AT hw-1) and <= the row's
    # hlen, so hashing the first `lim` columns covers every probe of
    # this batch; the columns past its longest host hash zeros nobody
    # reads.
    hmax = int(q_hlen[:b].max(initial=0))
    lim = min(hw - 1, hmax)
    hh = CK.rolling_fnv64(q_hostb[:b, :lim], host_salts)  # [lim+1,NS,b]
    # a window byte exists only below its row's hlen and only where the
    # hint has a host, so a DOT in the window is a probe wherever p >= 1
    wc = min(hw, hmax)
    dots = q_hostb[:b, :wc] == DOT
    dots[:, :1] = False
    exact = q_has_host[:b] & (q_hlen[:b] <= hw - 1)
    # flatnonzero walks row-major, i.e. each row's dots ascending: a
    # probe's place in its row is its rank among them, the exact slot
    # the place after the last dot
    dr, dc = np.divmod(np.flatnonzero(dots), max(wc, 1))
    nd = np.bincount(dr, minlength=b)
    need = int((nd + exact).max(initial=0))
    # a row holds at most hw <= MAX_HOST + 1 probes: the last tier
    # covers any batch
    maxp = next((t for t in MAXP_TIERS if t >= need), MAXP_TIERS[-1])
    er = np.flatnonzero(exact)
    pr = np.concatenate([dr, er])          # a probe's row,
    pl = np.concatenate([dc, q_hlen[er]])  # its length
    at = pr * maxp + np.concatenate([      # and its cell in [cap, maxp]
        np.arange(dr.size) - np.repeat(np.cumsum(nd) - nd, nd), nd[er]])
    ns = len(host_salts)
    hp_len, hp_slots = arena.probes(maxp)
    hp_len.reshape(-1)[at] = pl
    slot = (hh.reshape(lim + 1, ns * b)[pl, np.arange(ns)[:, None] * b + pr]
            & np.uint64(host_cap - 1)).astype(np.int32)  # [ns, probes]
    for k in range(ns):
        hp_slots[k].reshape(-1)[at] = slot[k]

    # --- uri probes at each rule-uri length <= query len: the same
    # hash columns for every row, so one gather of whole columns
    lens = np.full(lset_w, -1, np.int32)
    lens[: len(lset)] = lset
    ulim = min(uw, int(q_ulen[:b].max(initial=0)), max(lset, default=0))
    uh = CK.rolling_fnv64(q_urib[:b, :ulim], uri_salts)  # [ulim+1,NS,b]
    lv = (lens[None, :] >= 0) & (lens[None, :] <= q_ulen[:b, None]) & \
        q_has_uri[:b, None]
    c["up_len"][:b] = np.where(lv, lens[None, :], -1)
    # a length past ulim is valid for no row; clip keeps the gather in
    c["up_slots"][:, :b] = np.where(
        lv[None],
        (uh[np.clip(lens, 0, ulim)].transpose(1, 2, 0)
         & np.uint64(uri_cap - 1)).astype(np.int32), -1)
    return arena


# the python-int FNV form lives in ops/cuckoo (single source for the
# bit-identity-critical constants); aliased for the hot loop below
_FNV64_MASK = CK._M64
_FNV64_PRIME_I = CK._FNV64_PRIME_I
_FNV64_OFFSET_I = CK._FNV64_OFFSET_I
# below this batch size the per-hint pure-python encoder wins: the
# vectorized rolling-FNV pass costs ~W sequential numpy calls whose
# per-call overhead dwarfs the math on accept-path-sized batches
# (measured 309us numpy vs ~60us python at b=8, 20k rules). The
# PR-6 crossover of 32 was measured against the 5-op dispatch chain;
# re-measured under the fused dispatch (round 12, sandbox CPU, both 20k
# and 200k tables) the python path's advantage ends at ~28 (b=24: 268
# vs 316us; b=28: 324 vs 328us; b=30: 328 vs 318us; b=32: 573 vs
# 346us) — the fused launch removed enough dispatch overhead that
# encode is a larger share of the batch, and the numpy pass amortizes
# sooner than the old 32 default assumed.
# All of these crossovers were measured against the vectorized path as
# it was before PR 32 (a per-hint fill, one 63-column hash pass a salt);
# that path now costs about half at b=32 (sandbox CPU). The constant
# was kept, not re-measured: where the crossover lies now is a later
# change's to find.
SMALL_ENCODE = int(os.environ.get("VPROXY_TPU_SMALL_ENCODE", "28"))


def _encode_hint_queries_small(hints: Sequence, tab: HashHintTable,
                               pad_to: int,
                               slots: bool = False) -> QueryArena:
    """Per-hint python encoder, bit-identical outputs to the vectorized
    path (same probe order: dot suffixes ascending, exact slot last;
    same shapes: MAXP tier + lset_cap widths; the same arena, byte for
    byte), O(bytes) python ints instead of O(W) numpy dispatches."""
    b = len(hints)
    cap = max(b, pad_to)
    W = tab.hw
    arena = _HintArena(cap, W, tab.uw, tab.caps["lset"], slots=slots)
    c = arena.cols
    q_hostb, q_hlen, q_has_host = c["hostb"], c["hlen"], c["has_host"]
    q_urib, q_ulen, q_has_uri = c["urib"], c["ulen"], c["has_uri"]
    q_port = c["port"]

    s1, s2 = int(tab.host_salts[0]), int(tab.host_salts[1])
    us1, us2 = int(tab.uri_salts[0]), int(tab.uri_salts[1])
    hmask = tab.host_cap - 1
    umask = tab.uri_cap - 1
    probes: list[list] = []  # per hint: [(plen, slot1, slot2)]
    uprobes: list[list] = []  # per hint: [(lset_pos, plen, s1, s2)]
    need = 0
    for i, h in enumerate(hints):
        pr: list = []
        if h.host is not None:
            hb = h.host.encode()[::-1]
            hl = min(len(hb), 1 << 20)
            q_hlen[i] = hl
            win = hb[:W]
            q_hostb[i, : len(win)] = np.frombuffer(win, np.uint8)
            q_has_host[i] = True
            # one python pass: rolling FNV64 pair + dot probes
            h1 = _FNV64_OFFSET_I ^ s1
            h2 = _FNV64_OFFSET_I ^ s2
            lim = min(len(hb), W - 1)
            for p in range(lim):
                by = hb[p]
                if by == DOT and 1 <= p < hl:
                    pr.append((p, h1 & hmask, h2 & hmask))
                h1 = ((h1 ^ by) * _FNV64_PRIME_I) & _FNV64_MASK
                h2 = ((h2 ^ by) * _FNV64_PRIME_I) & _FNV64_MASK
            # boundary dot at position lim (a dot can be a probe
            # position without its byte being hashed into the prefix)
            if lim < len(hb) and lim < W and hb[lim] == DOT \
                    and 1 <= lim < hl:
                pr.append((lim, h1 & hmask, h2 & hmask))
            if hl <= W - 1:  # exact slot, last (vectorized order)
                pr.append((hl, h1 & hmask, h2 & hmask))
        probes.append(pr)
        need = max(need, len(pr))
        upr: list = []
        if h.uri is not None:
            ub = h.uri.encode()
            ul = min(len(ub), 1 << 20)
            q_ulen[i] = ul
            uwin = ub[: tab.uw]
            q_urib[i, : len(uwin)] = np.frombuffer(uwin, np.uint8)
            q_has_uri[i] = True
            u1 = _FNV64_OFFSET_I ^ us1
            u2 = _FNV64_OFFSET_I ^ us2
            pos = 0
            for li, l in enumerate(tab.lset):
                if l > ul:
                    break
                while pos < l:  # lset ascending: resume the roll
                    by = uwin[pos] if pos < len(uwin) else 0
                    u1 = ((u1 ^ by) * _FNV64_PRIME_I) & _FNV64_MASK
                    u2 = ((u2 ^ by) * _FNV64_PRIME_I) & _FNV64_MASK
                    pos += 1
                upr.append((li, l, u1 & umask, u2 & umask))
        uprobes.append(upr)
        q_port[i] = h.port

    maxp = next((t for t in MAXP_TIERS if t >= need), MAXP_TIERS[-1])
    hp_len, (hp_slot1, hp_slot2) = arena.probes(maxp)
    for i, pr in enumerate(probes):
        for j, (plen, sl1, sl2) in enumerate(pr[:maxp]):
            hp_len[i, j] = plen
            hp_slot1[i, j] = sl1
            hp_slot2[i, j] = sl2
    up_len, (up_slot1, up_slot2) = c["up_len"], c["up_slots"]
    for i, upr in enumerate(uprobes):
        for (li, l, sl1, sl2) in upr:
            up_len[i, li] = l
            up_slot1[i, li] = sl1
            up_slot2[i, li] = sl2
    return arena.queries()


def encode_hint_queries(hints: Sequence, tab: HashHintTable,
                        pad_to: int = 0, slots: bool = False) -> QueryArena:
    """Hints -> device-ready query dict incl. precomputed probe slots,
    its columns the views of one arena (QueryArena: what a served launch
    is handed). slots: leave room in it for the Maglev slot column of
    the fused programs (the result's `slots`, zero: the caller fills it).

    Host-side work is vectorized numpy (_encode_hint_arrays): the batch
    becomes arrays at the first step, then one rolling-FNV walk over the
    reversed host window and one over the uri window — both salts at
    once, only as many columns as the batch's longest host / uri — give
    every suffix/prefix hash; probe positions are the dots (host) and
    the table's rule-uri length set (uri). Batches up to SMALL_ENCODE
    take the per-hint python path instead (same outputs, cheaper at
    accept-path batch sizes). pad_to: emit arrays at this batch bucket,
    pad rows being invalid probes (never encode padding).
    """
    if len(hints) <= SMALL_ENCODE:
        return _encode_hint_queries_small(hints, tab,
                                          max(pad_to, len(hints)), slots)
    return _encode_hint_arrays(
        hints, max(len(hints), pad_to), tab.hw, tab.uw,
        tab.host_salts, tab.host_cap, tab.uri_salts, tab.uri_cap,
        tab.lset, tab.caps["lset"], slots).queries()


def _probe_buckets(slots, plen, used, klen, kbytes, bs, bc, qbytes, iota):
    """Byte-verified cuckoo probe -> candidate rule indices.

    slots/plen: [B, P] (slot -1 / len -1 = invalid); table arrays used
    [C], klen [C], kbytes [C, K], bs/bc [C]; qbytes [B, K'] query window
    (K' >= K); iota [BK]. -> [B, P, BK] candidate indices (-1 = none).
    """
    k = kbytes.shape[1]
    s = jnp.maximum(slots, 0)
    ok = (slots >= 0) & used[s] & (klen[s] == plen)
    kb = kbytes[s]  # [B, P, K]
    span = jnp.arange(k, dtype=jnp.int32)
    eq = (kb == qbytes[:, None, :k]) | (span[None, None, :] >= plen[:, :, None])
    ok = ok & jnp.all(eq, axis=-1)
    start, cnt = bs[s], bc[s]
    j = iota[None, None, :]
    return jnp.where(ok[:, :, None] & (j < cnt[:, :, None]),
                     start[:, :, None] + j, -1)


# largest r_cap whose (level, index) pair still packs into one i32
# (max level = (3 << HOST_SHIFT) + URI_MAX_SCORE = 4095)
_PACK_I32_MAX = (2**31 - 1) // 4096 - 1


def _reduce_best(level, c, r_cap: int):
    """(max level, min index among level-winners) -> (idx, level).
    Small tables keep the single-reduction i32 packing; past
    _PACK_I32_MAX (a million-rule single table — the fused path's
    scale tier) the packed product would overflow i32, so the same
    winner comes from two reductions. Static branch (r_cap is a trace
    constant): zero cost for the small case, identical winners in
    both."""
    if r_cap <= _PACK_I32_MAX:
        pack = jnp.where(level > 0, level * (r_cap + 1) + (r_cap - c), 0)
        best = jnp.max(pack, axis=1)
        best_level = best // (r_cap + 1)
        best_idx = r_cap - best % (r_cap + 1)
        return jnp.where(best > 0, best_idx, -1).astype(jnp.int32), \
            best_level.astype(jnp.int32)
    best_level = jnp.max(level, axis=1)
    cand = jnp.where((level == best_level[:, None]) & (level > 0), c,
                     r_cap)
    best_idx = jnp.min(cand, axis=1)
    return jnp.where(best_level > 0, best_idx, -1).astype(jnp.int32), \
        best_level.astype(jnp.int32)


def hint_hash_match(t: dict, q: dict):
    """-> (best rule idx [B] i32 or -1, best level [B] i32).

    Candidates from host/uri probes + wildcard lists, scored with the
    full Hint.matchLevel formula from gathered rule records.
    """
    r_cap = t["r_active"].shape[0]
    b = q["hostb"].shape[0]

    # the stages carry jax.named_scope names (metadata only: the compiled
    # program is the same) so a profile can say which stage an op is of
    with jax.named_scope("hint_probe"):       # slot gathers + byte-verify
        htab = (t["hk_used"], t["hk_len"], t["hk_bytes"], t["hk_bs"],
                t["hk_bc"], q["hostb"], t["bh_iota"])
        utab = (t["uk_used"], t["uk_len"], t["uk_bytes"], t["uk_bs"],
                t["uk_bc"], q["urib"], t["bu_iota"])
        ch1 = _probe_buckets(q["hp_slot1"], q["hp_len"], *htab)
        ch2 = _probe_buckets(q["hp_slot2"], q["hp_len"], *htab)
        cu1 = _probe_buckets(q["up_slot1"], q["up_len"], *utab)
        cu2 = _probe_buckets(q["up_slot2"], q["up_len"], *utab)
    with jax.named_scope("hint_candidates"):  # bucket items -> rule ids
        def items(of, ch):
            return jnp.where(ch >= 0, t[of][jnp.maximum(ch, 0)], -1)
        host_cand, host_cand2 = items("hb_items", ch1), items("hb_items", ch2)
        uri_cand, uri_cand2 = items("ub_items", cu1), items("ub_items", cu2)

        cand = jnp.concatenate([
            host_cand.reshape(b, -1), host_cand2.reshape(b, -1),
            uri_cand.reshape(b, -1), uri_cand2.reshape(b, -1),
            jnp.broadcast_to(t["wh_idx"][None], (b, t["wh_idx"].shape[0])),
            jnp.broadcast_to(t["wu_idx"][None], (b, t["wu_idx"].shape[0])),
        ], axis=1)  # [B, NC]

        c = jnp.maximum(cand, 0)
        valid = (cand >= 0) & t["r_active"][c]

    with jax.named_scope("hint_score"):       # rule-record gathers + compare
        # port gate (Hint.java: ports both set and different -> no match)
        rp = t["r_port"][c]
        pg = (q["port"][:, None] == 0) | (rp == 0) | \
            (q["port"][:, None] == rp)

        # host level: exact=3 / dot-suffix=2 / wildcard=1 (max of applicable)
        hw = t["r_host"].shape[1]
        hk, hl_ = t["r_host_kind"][c], t["r_host_len"][c]
        rb = t["r_host"][c]  # [B, NC, hw]
        span = jnp.arange(hw, dtype=jnp.int32)
        heq = jnp.all((rb == q["hostb"][:, None, :hw]) |
                      (span[None, None, :] >= hl_[:, :, None]), axis=-1)
        exact = heq & (hl_ == q["hlen"][:, None])
        boundary = jnp.take_along_axis(
            q["hostb"], jnp.clip(hl_, 0, hw - 1), axis=1)
        suffix = heq & (hl_ < q["hlen"][:, None]) & (boundary == DOT)
        host_level = jnp.maximum(
            jnp.maximum(jnp.where(exact, 3, 0), jnp.where(suffix, 2, 0)),
            jnp.where(hk == 2, 1, 0))
        host_level = jnp.where((hk > 0) & q["has_host"][:, None],
                               host_level, 0)

        # uri level: exact/prefix -> min(len(rule.uri)+1, 1023), wildcard -> 1
        uw = t["r_uri"].shape[1]
        uk, ul = t["r_uri_kind"][c], t["r_uri_len"][c]
        ub = t["r_uri"][c]  # [B, NC, uw]
        uspan = jnp.arange(uw, dtype=jnp.int32)
        ueq = jnp.all((ub == q["urib"][:, None, :]) |
                      (uspan[None, None, :] >= ul[:, :, None]), axis=-1)
        prefix = ueq & (ul <= q["ulen"][:, None])
        uri_level = jnp.maximum(jnp.where(prefix, t["r_uri_score"][c], 0),
                                jnp.where(uk == 2, 1, 0))
        uri_level = jnp.where((uk > 0) & q["has_uri"][:, None],
                              uri_level, 0)

        level = (host_level << HOST_SHIFT) + uri_level
        level = jnp.where(valid & pg, level, 0)
    with jax.named_scope("hint_reduce"):      # best level, first index
        return _reduce_best(level, c, r_cap)


# --------------------------------------------------------------- cidr side


def _expand_patterns(net) -> list:
    """Network -> [(key16, mask16, family)] reproducing Network.maskMatch
    (Network.java:183-278) — same cases as tables._expand_cidr."""
    ip, mask = net.ip, net.mask
    out = []

    def mk(key, m, fam):
        out.append((bytes(np.frombuffer(bytes(key), np.uint8) &
                          np.frombuffer(bytes(m), np.uint8)), bytes(m), fam))

    if len(ip) == 4:
        mk(b"\x00" * 12 + ip, b"\x00" * 12 + mask, V4)
        mk(b"\x00" * 12 + ip, b"\xff" * 12 + mask, V6)
        mk(b"\x00" * 10 + b"\xff\xff" + ip, b"\xff" * 12 + mask, V6)
    elif len(mask) == 4:
        mk(ip[:4] + b"\x00" * 12, mask + b"\x00" * 12, V6)
    else:
        mk(ip, mask, V6)
        hi_ok = all(b == 0 for b in ip[:10]) and ip[10:12] in (b"\x00\x00", b"\xff\xff")
        if hi_ok:
            mk(b"\x00" * 12 + ip[12:], b"\x00" * 12 + mask[12:], V4)
    return out


# entries of a bucket that ride in the cuckoo slot's own row of `b_rows`
# (module docstring); 16 holds the fattest bucket of the north-star ACL
BUCKET_INLINE = 16
NO_RULE = np.iinfo(np.int32).max  # a pad entry's index: it never wins


def _bucket_cap(n: int) -> int:
    """Bucket-width cap for a fattest bucket of n rules: a power of two
    while it fits one row, whole rows past that (so the hop count is
    ceil(n / BUCKET_INLINE), not its next power of two)."""
    if n <= BUCKET_INLINE:
        return _pow2(n, 1)
    return -(-n // BUCKET_INLINE) * BUCKET_INLINE


@dataclass
class HashCidrTable:
    n: int
    r_cap: int
    arrays: dict
    caps: dict = field(default_factory=dict)
    # what the install gauges publish (engine.cidr_bucket_stat): row
    # width, hops a lookup makes, used slots, and how many of them own
    # an overflow row
    buckets: dict = field(default_factory=dict)


def _fnv32_bytes(key: bytes, salt: int) -> int:
    return int(CK.fnv32_masked(np.frombuffer(key, np.uint8), salt))


def _bucket_rows(flat: np.ndarray, start: np.ndarray, count: np.ndarray,
                 k: int, ov: int, ports) -> tuple:
    """Slot buckets -> (b_rows [ct + ov, w * k] i32, b_next [ct + ov]
    i32). Row layout is planar: k rule indices, then
    (ACL tables, ports = (min_port, max_port) per rule) k range starts
    and k range ends. Pad entries hold NO_RULE and the empty range
    [1, 0]. `b_next` names the row a bucket continues in; a bucket's
    last row names itself, so a hop past the end reads that row again
    and the least index over the hops does not change."""
    ct = start.shape[0]
    idx = np.full((ct + ov, k), NO_RULE, np.int32)
    nxt = np.arange(ct + ov, dtype=np.int32)
    j = np.arange(k)
    inline = j[None, :] < count[:, None]
    idx[:ct][inline] = flat[(start[:, None] + j[None, :])[inline]]
    CK.coop_yield()
    r = ct
    for s in np.nonzero(count > k)[0]:
        prev = s
        for lo in range(k, int(count[s]), k):
            seg = flat[start[s] + lo: start[s] + min(lo + k, int(count[s]))]
            idx[r, : len(seg)] = seg
            nxt[prev] = prev = r
            r += 1
    if ports is None:
        return idx, nxt
    real = idx != NO_RULE
    at = np.where(real, idx, 0)
    CK.coop_yield()
    rows = np.concatenate([idx, np.where(real, ports[0][at], 1),
                           np.where(real, ports[1][at], 0)], axis=1)
    return rows, nxt


def compile_cidr_hash(networks: Sequence, acl: Optional[Sequence[AclRule]] = None,
                      caps: Optional[dict] = None) -> HashCidrTable:
    caps = dict(caps or {})
    n = len(networks)
    r_cap = caps.get("r_cap") or _pad_cap(n, 256)
    if n > r_cap:
        r_cap = _pad_cap(n, 256)

    groups: dict[tuple, dict[bytes, list[int]]] = {}
    for i, net in enumerate(networks):
        if not (i & 31):
            CK.coop_yield()
        for key, mask, fam in _expand_patterns(net):
            groups.setdefault((fam, mask), {}).setdefault(key, []).append(i)

    g_live = sorted(groups.keys())
    g_cap = max(caps.get("g_cap", 0), _pow2(max(len(g_live), 1), 8))
    if len(g_live) > g_cap:
        g_cap = _pow2(len(g_live), 8)

    g_fam = np.full(g_cap, -1, np.int32)
    g_mask = np.zeros((g_cap, 16), np.uint8)
    g_off = np.zeros(g_cap, np.int32)
    g_capmask = np.zeros(g_cap, np.int32)
    g_salt1 = np.zeros(g_cap, np.uint32)
    g_salt2 = np.zeros(g_cap, np.uint32)

    tabs = []
    flat_items: list[int] = []
    off = 0
    for gi, (fam, mask) in enumerate(g_live):
        t, items = CK.build_cuckoo(groups[(fam, mask)], 16,
                                   hasher=_fnv32_bytes, salt_base=3 + gi)
        g_fam[gi] = fam
        g_mask[gi] = np.frombuffer(mask, np.uint8)
        g_off[gi] = off
        g_capmask[gi] = t.cap - 1
        g_salt1[gi] = t.salt1
        g_salt2[gi] = t.salt2
        t.bucket_start += len(flat_items)
        flat_items.extend(items.tolist())
        tabs.append(t)
        off += t.cap

    ct = max(caps.get("ct", 0), _pow2(max(off, 1), 256))
    s_used = np.zeros(ct, bool)
    s_key = np.zeros((ct, 16), np.uint8)
    start = np.zeros(ct, np.int64)
    count = np.zeros(ct, np.int64)
    o = 0
    for t in tabs:
        s_used[o: o + t.cap] = t.used
        s_key[o: o + t.cap] = t.key_bytes
        start[o: o + t.cap] = t.bucket_start
        count[o: o + t.cap] = t.bucket_count
        o += t.cap

    bk = max(caps.get("bk", 1), _bucket_cap(int(count.max(initial=1))))
    k = min(bk, BUCKET_INLINE)
    hops = -(-bk // k)
    fat = count > k
    n_ov = int(((count[fat] - 1) // k).sum())
    ov = max(caps.get("ov", 0), _pow2(n_ov, 8) if n_ov else 0)

    allow = np.zeros(r_cap, bool)
    ports = None
    if acl is not None:
        ports = (np.zeros(r_cap, np.int32), np.zeros(r_cap, np.int32))
        for i, r in enumerate(acl):
            ports[0][i], ports[1][i], allow[i] = r.min_port, r.max_port, r.allow
    b_rows, b_next = _bucket_rows(np.asarray(flat_items, np.int32),
                                  start, count, k, ov, ports)

    arrays = {
        "g_fam": g_fam, "g_mask": g_mask, "g_off": g_off,
        "g_capmask": g_capmask, "g_salt1": g_salt1, "g_salt2": g_salt2,
        "s_used": s_used, "s_key": s_key, "b_rows": b_rows,
        # [hops, K]: the kernel's two static numbers, as a shape
        "b_shape": np.zeros((hops, k), np.int8),
        "allow": allow,
    }
    if hops > 1:
        arrays["b_next"] = b_next
    return HashCidrTable(n=n, r_cap=r_cap, arrays=arrays,
                         caps={"r_cap": r_cap, "g_cap": g_cap, "ct": ct,
                               "bk": bk, "ov": ov},
                         buckets={"width": k, "hops": hops,
                                  "used_slots": int(s_used.sum()),
                                  "overflow_slots": int(fat.sum())})


def _fnv32_device(masked: jnp.ndarray, salt: jnp.ndarray) -> jnp.ndarray:
    """masked [B, G, 16] u8, salt [G] (or [B, G]) u32 -> [B, G] u32;
    bit-identical to cuckoo.fnv32_masked (u32 wraparound multiply)."""
    seed = CK.FNV32_OFFSET ^ salt
    # a table set hands every lookup its own table's salts: [B, G]
    h = seed if seed.ndim == 2 else jnp.broadcast_to(seed[None, :],
                                                     masked.shape[:2])
    prime = jnp.uint32(CK.FNV32_PRIME)
    for p in range(16):
        h = (h ^ masked[:, :, p].astype(jnp.uint32)) * prime
    return h


def cidr_hash_match(t: dict, addr16: jnp.ndarray, fam: jnp.ndarray,
                    port: Optional[jnp.ndarray] = None,
                    tid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """-> first-matching rule index [B] i32 (ordered-scan semantics), -1
    if none. addr16 [B,16] u8, fam [B] i32, port [B] i32 (ACL only).
    tid [B] i32: the table each lookup names, where `t` holds a set of
    tables (stack_cidr_tables: the per-group rows carry a leading table
    axis, slots and bucket rows are flat under absolute offsets). A
    lookup then gathers its own table's group rows; with tid None the
    one table's rows broadcast and no such gather is traced."""
    if tid is None:
        def g(name):
            return t[name][None]
        salts = (t["g_salt1"], t["g_salt2"])
    else:
        def g(name):
            return t[name][tid]
        salts = (g("g_salt1"), g("g_salt2"))
    with jax.named_scope("cidr_mask"):      # per-group masked address
        masked = addr16[:, None, :] & g("g_mask")  # [B, G, 16]
        gok = (g("g_fam") >= 0) & (fam[:, None] == g("g_fam"))

    probes = []
    for salt in salts:
        with jax.named_scope("cidr_hash"):  # FNV over the 16 masked bytes
            h = _fnv32_device(masked, salt)
            slot = g("g_off") + (
                h.astype(jnp.int32) & g("g_capmask"))
        with jax.named_scope("cidr_probe"):  # slot gathers + key verify
            key = t["s_key"][slot]  # [B, G, 16]
            ok = gok & t["s_used"][slot] & jnp.all(key == masked, axis=-1)
            probes.append((slot, ok))
    with jax.named_scope("cidr_candidates"):    # a key sits in one slot
        (slot1, ok1), (slot2, ok2) = probes
        slot, hit = jnp.where(ok1, slot1, slot2), ok1 | ok2

    # the hit slot's bucket: one row gather a hop; the port gate and the
    # reduce run on the gathered row
    hops, k = t["b_shape"].shape
    gated = port is not None and t["b_rows"].shape[1] > k  # a route
    #                            table has no range columns: every port
    first = jnp.full(addr16.shape[0], NO_RULE, jnp.int32)
    for hop in range(hops):
        with jax.named_scope("cidr_candidates"):
            row = t["b_rows"][slot]  # [B, G, w * K]
        with jax.named_scope("cidr_gate"):      # port range
            ok = hit[:, :, None]
            if gated:
                p = port[:, None, None]
                ok = ok & (row[..., k: 2 * k] <= p) & (p <= row[..., 2 * k:])
        with jax.named_scope("cidr_reduce"):    # first match = least index
            first = jnp.minimum(first, jnp.min(
                jnp.where(ok, row[..., :k], NO_RULE), axis=(1, 2)))
        if hop + 1 < hops:
            with jax.named_scope("cidr_candidates"):
                slot = t["b_next"][slot]
    with jax.named_scope("cidr_reduce"):
        return jnp.where(first != NO_RULE, first, -1)


def classify_hash_all(hint_t: dict, route_t: dict, acl_t: dict,
                      hint_q: dict, addr16: jnp.ndarray, fam: jnp.ndarray,
                      port: jnp.ndarray) -> jnp.ndarray:
    """The fused flagship step: one dispatch classifies a micro-batch of
    LB/DNS hints + route LPM + ACL checks; one packed [B, 3] i32 result
    so the host pays a single d2h per step."""
    with jax.named_scope("hint"):
        h_idx, _ = hint_hash_match(hint_t, hint_q)
    with jax.named_scope("route"):
        r_idx = cidr_hash_match(route_t, addr16, fam, None)
    with jax.named_scope("acl"):
        a_idx = cidr_hash_match(acl_t, addr16, fam, port)
    return jnp.stack([h_idx, r_idx, a_idx], axis=1)


# ------------------------------------------------------- a set of tables
#
# Many ordered CIDR tables behind ONE program (a switch's RouteTable a
# VNI): each table is compiled on its own (compile_cidr_hash, so a
# change to one rebuilds one), and stack_cidr_tables lays the compiled
# tables side by side — the small per-group rows stacked on a leading
# table axis, the cuckoo slots and their bucket rows concatenated with
# every group offset made absolute, overflow rows behind all slots.
# Only the bucket width K and the hop count are unified (a narrower
# table's rows are padded with entries that never win). A lookup names
# its table; cidr_hash_match gathers that table's group rows by `tid`
# and is otherwise the program a single table runs.


def _slot_extent(a: dict) -> int:
    """Slots a compiled table's groups really span (its `ct` is padded)."""
    live = a["g_fam"] >= 0
    return int((a["g_off"] + a["g_capmask"] + 1)[live].max(initial=0))


def _widen_rows(rows: np.ndarray, k: int, K: int, planes: int) -> np.ndarray:
    """Bucket rows [n, w * k] (w = 1: indices; 3: indices, range starts,
    range ends) -> [n, planes * K]; pad entries hold NO_RULE and the
    empty range [1, 0], a route table's entries every port."""
    n, w = rows.shape[0], rows.shape[1] // k
    out = np.empty((n, planes, K), np.int32)
    out[:, 0] = NO_RULE
    out[:, 0, :k] = rows[:, :k]
    if planes == 3:
        out[:, 1], out[:, 2] = 1, 0
        if w == 3:
            out[:, 1, :k] = rows[:, k: 2 * k]
            out[:, 2, :k] = rows[:, 2 * k:]
        else:
            real = rows != NO_RULE
            out[:, 1, :k] = np.where(real, 0, 1)
            out[:, 2, :k] = np.where(real, 65535, 0)
    return out.reshape(n, planes * K)


def stack_cidr_tables(tabs: Sequence[Optional[HashCidrTable]],
                      caps: Optional[dict] = None) -> tuple:
    """tabs[i] = table id i's compiled table, None for an id no table
    holds -> (arrays, caps, buckets) of the set. caps only grow, and
    every shape is padded to them, so a change that fits traces nothing
    new: t_cap tables, g_cap groups a table, ct slots, ov overflow
    rows, bk the fattest bucket (width and hops)."""
    caps = dict(caps or {})
    live = [t for t in tabs if t is not None]
    ext = [0 if t is None else _slot_extent(t.arrays) for t in tabs]
    ovs = [0 if t is None else t.arrays["b_rows"].shape[0]
           - t.arrays["s_key"].shape[0] for t in tabs]
    t_cap = max(caps.get("t_cap", 0), _pow2(max(len(tabs), 1), 8))
    g_cap = max([caps.get("g_cap", 8)]
                + [t.arrays["g_fam"].shape[0] for t in live])
    ct = max(caps.get("ct", 0), _pow2(max(sum(ext), 1), 256))
    ov = max(caps.get("ov", 0), _pow2(sum(ovs), 8) if sum(ovs) else 0)
    bk = max([caps.get("bk", 1)] + [t.caps["bk"] for t in live])
    K = min(bk, BUCKET_INLINE)
    hops = -(-bk // K)
    planes = 3 if any(t.arrays["b_rows"].shape[1]
                      > t.arrays["b_shape"].shape[1] for t in live) else 1
    planes = max(planes, caps.get("planes", 1))

    g_fam = np.full((t_cap, g_cap), -1, np.int32)
    g_mask = np.zeros((t_cap, g_cap, 16), np.uint8)
    g_off = np.zeros((t_cap, g_cap), np.int32)
    g_capmask = np.zeros((t_cap, g_cap), np.int32)
    g_salt1 = np.zeros((t_cap, g_cap), np.uint32)
    g_salt2 = np.zeros((t_cap, g_cap), np.uint32)
    s_used = np.zeros(ct, bool)
    s_key = np.zeros((ct, 16), np.uint8)
    b_rows = np.empty((ct + ov, planes * K), np.int32)
    b_rows[:] = _widen_rows(np.full((1, 1), NO_RULE, np.int32), 1, K,
                            planes)
    b_next = np.arange(ct + ov, dtype=np.int32)
    base, obase = 0, ct
    for i, t in enumerate(tabs):
        if t is None:
            continue
        CK.coop_yield()
        a, n, no = t.arrays, ext[i], ovs[i]
        g = a["g_fam"].shape[0]
        g_fam[i, :g], g_mask[i, :g] = a["g_fam"], a["g_mask"]
        g_off[i, :g] = a["g_off"] + base
        g_capmask[i, :g] = a["g_capmask"]
        g_salt1[i, :g], g_salt2[i, :g] = a["g_salt1"], a["g_salt2"]
        s_used[base: base + n] = a["s_used"][:n]
        s_key[base: base + n] = a["s_key"][:n]
        k = a["b_shape"].shape[1]
        own_ct = a["s_key"].shape[0]
        b_rows[base: base + n] = _widen_rows(a["b_rows"][:n], k, K, planes)
        if no:
            b_rows[obase: obase + no] = _widen_rows(a["b_rows"][own_ct:],
                                                    k, K, planes)
            nxt = a["b_next"].astype(np.int64)
            nxt = np.where(nxt < own_ct, nxt + base, nxt - own_ct + obase)
            b_next[base: base + n] = nxt[:n]
            b_next[obase: obase + no] = nxt[own_ct:]
        base += n
        obase += no
    arrays = {
        "g_fam": g_fam, "g_mask": g_mask, "g_off": g_off,
        "g_capmask": g_capmask, "g_salt1": g_salt1, "g_salt2": g_salt2,
        "s_used": s_used, "s_key": s_key, "b_rows": b_rows,
        "b_shape": np.zeros((hops, K), np.int8),
    }
    if hops > 1:
        arrays["b_next"] = b_next
    return (arrays,
            {"t_cap": t_cap, "g_cap": g_cap, "ct": ct, "ov": ov, "bk": bk,
             "planes": planes},
            {"width": K, "hops": hops,
             "used_slots": sum(t.buckets["used_slots"] for t in live),
             "overflow_slots": sum(t.buckets["overflow_slots"]
                                   for t in live)})


def cidr_set_match(t: dict, addr16: jnp.ndarray, fam: jnp.ndarray,
                   tid: jnp.ndarray,
                   port: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """cidr_hash_match over a set of tables (stack_cidr_tables): lookup
    b is answered from table tid[b], by that table's own rule indices."""
    return cidr_hash_match(t, addr16, fam, port, tid)


def jit_packed(name: str, body):
    """jit a served program, body(*tables, buf, layout), whose query is
    one arena (unpack_arena its first lines), under `name`: the compiled
    program is `jit_<name>` in a device trace, and the benchmark's
    kernel metrics find it by that."""
    body.__name__ = body.__qualname__ = name
    return jax.jit(body, static_argnames="layout")


def _hint_packed(t: dict, buf, layout: ArenaLayout):
    return hint_hash_match(t, unpack_hint_arena(buf, layout)[0])


def _cidr_packed(match):
    def packed(t: dict, buf, layout: ArenaLayout):
        q = unpack_arena(buf, layout)
        return match(t, q["a16"], q["fam"], port=q.get("port"),
                     tid=q.get("tid"))
    return packed


# the "jax" backend's served entries: (tables, arena, layout)
hint_hash_jit = jit_packed("hint_hash_match", _hint_packed)
cidr_hash_jit = jit_packed("cidr_hash_match", _cidr_packed(cidr_hash_match))
cidr_set_jit = jit_packed("cidr_set_match", _cidr_packed(cidr_set_match))
classify_hash_jit = jax.jit(classify_hash_all)


# ----------------------------------------------------- mesh-sharded path
#
# Rule-axis sharding for the hash path: the rule list is split into S
# contiguous slices, each compiled into its OWN cuckoo table (hash
# probing is slot-local, so sharding the compiled arrays directly would
# turn every probe into a cross-device gather). All shards share one
# unified `caps` dict, so the per-shard arrays have identical shapes and
# stack along a leading shard axis that carries the mesh's "rules"
# PartitionSpec. Each device runs the UNCHANGED single-shard kernel on
# its local slice inside shard_map; the global winner is a two-phase
# collective reduction (pmax best-level, then pmin global-index among
# level-winners — exactly Upstream.java:187's strictly-greater-max +
# earliest-index-tie semantics, distributed).


@dataclass
class ShardedHashTable:
    """S per-shard tables with unified shapes, stacked for the mesh."""

    shards: list  # per-shard HashHintTable | HashCidrTable
    arrays: dict  # stacked [S, ...] numpy arrays
    shard_size: int  # rules per shard (global idx = shard * size + local)
    n: int
    r_cap: int  # per-shard capacity
    # hint tables only (compile_hint_hash_sharded): the sorted union of
    # the shards' rule-uri length sets, precomputed so the single-pass
    # encoder does no per-dispatch set algebra; None for cidr/foreign
    # stabs (the encoder falls back to the legacy per-shard path)
    lset_u: Optional[list] = None


def _unify_caps(tabs_caps: list) -> dict:
    out: dict = {}
    for c in tabs_caps:
        for k, v in c.items():
            out[k] = max(out.get(k, 0), v)
    return out


class CapsExceeded(Exception):
    """A caps-reusing recompile outgrew the reused shapes — the caller's
    no-retrace update contract cannot hold; rebuild tables + fn."""


def _compile_sharded(items: Sequence, n_shards: int, compile_one,
                     caps: Optional[dict]) -> ShardedHashTable:
    """compile_one(slice, item_offset, caps) -> per-shard table; the
    offset is the slice's start index in `items`, so positional side
    tables (ACL windows) stay aligned with the slicing by construction.
    When caps is supplied (the runtime-update fast path), the result
    MUST fit: growth raises CapsExceeded instead of silently changing
    shapes and retracing the caller's jitted classify.

    Memory-lean: once the per-shard arrays are stacked, the per-shard
    copies are dropped (the shard objects stay — encoders read their
    salts/caps/lset, never the arrays). A 1M-rule table would otherwise
    sit in host RAM twice before it ever reaches the device."""
    reused = dict(caps) if caps else None
    per = max(1, -(-len(items) // n_shards))  # ceil; empty tail shards ok
    slices = [list(items[d * per: (d + 1) * per]) for d in range(n_shards)]
    caps = dict(caps or {})
    for _ in range(6):  # caps only grow; fixed point in a few rounds
        tabs = []
        for d, s in enumerate(slices):
            tabs.append(compile_one(s, d * per, caps))
            CK.coop_yield()  # standby-compile courtesy: explicit
            #                  preemption point between shard builds
        merged = _unify_caps([t.caps for t in tabs])
        if all(t.caps == merged for t in tabs):
            if reused is not None and merged != reused:
                raise CapsExceeded(
                    f"update outgrew reused caps: {reused} -> {merged}")
            arrays = {}
            for k in tabs[0].arrays:
                CK.coop_yield()  # stack chunks are multi-MB memcpys:
                #                  paced per key like the build loops
                arrays[k] = np.stack([t.arrays[k] for t in tabs])
            for t in tabs:
                t.arrays = {}
            return ShardedHashTable(shards=tabs, arrays=arrays,
                                    shard_size=per, n=len(items),
                                    r_cap=tabs[0].r_cap)
        caps = merged
    raise RuntimeError("sharded table caps did not converge")


def compile_hint_hash_sharded(rules: Sequence[HintRule], n_shards: int,
                              caps: Optional[dict] = None) -> ShardedHashTable:
    """Per-shard compiles under unified caps, plus the UNION uri-length
    cap ("lset_u") the single-pass sharded encoder sizes its probe axis
    by: a caps-stable width, so same-caps rule updates keep one query
    trace shape (the no-retrace contract) — an update whose uri-length
    union outgrows it raises CapsExceeded like any other caps growth
    (the engine transparently rebuilds + retraces once)."""
    reused_u = (caps or {}).get("lset_u")
    inner = dict(caps) if caps else None
    if inner is not None:
        inner.pop("lset_u", None)  # per-shard compiles don't know it
    stab = _compile_sharded(
        rules, n_shards,
        lambda s, off, caps: compile_hint_hash(s, caps=caps), inner)
    union = set()
    for t in stab.shards:
        union.update(t.lset)
    u_cap = _pow2(max(len(union), 1), 4)
    if reused_u:
        if u_cap > reused_u:
            raise CapsExceeded(
                f"uri-length union outgrew reused cap: {reused_u} -> "
                f"{u_cap}")
        u_cap = reused_u
    for t in stab.shards:
        t.caps["lset_u"] = u_cap
    stab.lset_u = sorted(union)
    return stab


def compile_cidr_hash_sharded(networks: Sequence, n_shards: int,
                              acl: Optional[Sequence[AclRule]] = None,
                              caps: Optional[dict] = None) -> ShardedHashTable:
    # each shard's ACL window follows its rule slice positionally (the
    # offset comes FROM the slicer, so they cannot drift apart)
    return _compile_sharded(
        networks, n_shards,
        lambda s, off, caps: compile_cidr_hash(
            s, acl=None if acl is None else acl[off: off + len(s)],
            caps=caps), caps)


def encode_hint_queries_sharded(hints: Sequence, stab: ShardedHashTable,
                                pad_to: Optional[int] = None) -> dict:
    """Per-shard probe encoding stacked on the leading shard axis.

    Probe slots/salts are shard-local, so the same hint batch encodes
    differently per shard — but only in the HASH VALUES: the unified
    caps guarantee every shard shares the compare windows and table
    capacities, and the probe POSITIONS (dots, uri lengths) depend only
    on query content. So this runs the byte walk and the rolling-FNV
    pass ONCE for all shards (_encode_hint_arrays over the shards'
    salts), instead of the S sequential re-encodes the original path
    paid — measured 8x of the whole dispatch's host cost at S=8.

    uri probes ride the UNION of the shards' rule-uri length sets: a
    probe at a length some shard lacks byte-verifies off (no key of
    that length exists there), so correctness is per-shard exact while
    the probe arrays stay shard-uniform.

    pad_to: encode the real hints only and zero/-1-fill the probe rows
    up to the batch bucket (a pad row has no probes and can never
    match). Each device still receives only its own slice (the stacked
    dims are sharded (rules, batch) on the mesh)."""
    shards = stab.shards
    t0 = shards[0]
    # compile_hint_hash_sharded guarantees unified shard shapes and
    # precomputes the uri-length union; a foreign-built stab (no
    # lset_u) pays the uniformity scan once per dispatch or drops to
    # the legacy per-shard encode
    if stab.lset_u is None and not all(
            t.hw == t0.hw and t.uw == t0.uw
            and t.host_cap == t0.host_cap and t.uri_cap == t0.uri_cap
            for t in shards):
        # non-unified shard shapes (foreign-built stab): legacy path
        if pad_to and pad_to > len(hints):
            from ..rules.ir import Hint
            hints = list(hints) + [Hint()] * (pad_to - len(hints))
        per = [encode_hint_queries(hints, t) for t in shards]
        return {k: np.stack([p[k] for p in per]) for k in per[0]}

    S = len(shards)
    # --- uri probes ride the UNION of the shards' rule-uri length sets;
    # width = the caps-stable "lset_u" cap (compile_hint_hash_sharded)
    # so caps-reusing updates keep ONE query trace shape
    lset_u = stab.lset_u if stab.lset_u is not None else sorted(
        set().union(*[set(t.lset) for t in shards]))
    lw = t0.caps.get("lset_u") or _pow2(max(len(lset_u), 1), 4)
    # salt s of every shard, then salt s+1 of every shard: rows [:S] of
    # the slot arrays are the shards' first-salt slots, [S:] the second
    c = _encode_hint_arrays(
        hints, max(len(hints), pad_to or 0), t0.hw, t0.uw,
        [t.host_salts[k] for k in (0, 1) for t in shards], t0.host_cap,
        [t.uri_salts[k] for k in (0, 1) for t in shards], t0.uri_cap,
        lset_u, lw).cols
    hs, us = c["hp_slots"], c["up_slots"]
    # shard-invariant keys: a zero-stride broadcast view on the shard
    # axis (device_put materializes each device's slice)
    return {**{k: np.broadcast_to(c[k], (S,) + c[k].shape)
               for k in _HINT_COLS},
            "hp_slot1": hs[:S], "hp_slot2": hs[S:],
            "up_slot1": us[:S], "up_slot2": us[S:]}
