"""Hash-path classify kernels vs the pure-Python oracle.

The cuckoo/hash matchers (ops/hashmatch.py) are the production fast
path; every semantic subtlety of Hint.matchLevel / ordered CIDR
first-match that the dense matchers are tested for must hold here too,
plus hash-specific ones: bucket sharing (many rules, one key), suffix
probe positions, rebuild-on-update with cap reuse, wildcard keys.
"""
import random

import numpy as np
import pytest

from vproxy_tpu.ops import hashmatch as H
from vproxy_tpu.ops import tables as T
from vproxy_tpu.rules import oracle
from vproxy_tpu.rules.engine import CidrMatcher, HintMatcher, pad_batch
from vproxy_tpu.rules.ir import (AclRule, Hint, HintRule, Proto, RouteRule,
                                 RouteTable)
from vproxy_tpu.utils.ip import Network, mask_bytes, parse_ip

rnd = random.Random(1234)

WORDS = ["a", "bb", "ccc", "x", "api", "web", "cdn", "img", "v2", "svc"]
TLDS = ["com", "net", "io", "local"]


def rand_domain():
    n = rnd.randint(1, 3)
    return ".".join(rnd.choice(WORDS) for _ in range(n)) + "." + rnd.choice(TLDS)


def rand_uri():
    n = rnd.randint(1, 4)
    return "/" + "/".join(rnd.choice(WORDS) for _ in range(n))


def rand_hint_rule():
    host = uri = None
    port = 0
    while host is None and uri is None and port == 0:
        if rnd.random() < 0.7:
            host = "*" if rnd.random() < 0.1 else rand_domain()
        if rnd.random() < 0.5:
            uri = "*" if rnd.random() < 0.1 else rand_uri()
        if rnd.random() < 0.3:
            port = rnd.choice([80, 443, 8080])
    return HintRule(host=host, port=port, uri=uri)


def rand_hint():
    host = rand_domain() if rnd.random() < 0.8 else None
    if host and rnd.random() < 0.5:
        host = rnd.choice(WORDS) + "." + host
    uri = rand_uri() if rnd.random() < 0.6 else None
    port = rnd.choice([0, 80, 443, 8080])
    return Hint(host=host, port=port, uri=uri)


def check_hints(rules, hints):
    tab = H.compile_hint_hash(rules)
    q = H.encode_hint_queries(hints, tab)
    idx, level = H.hint_hash_match(tab.arrays, q)
    idx, level = np.asarray(idx), np.asarray(level)
    for i, h in enumerate(hints):
        want = oracle.search(rules, h)
        assert idx[i] == want, (i, h, int(idx[i]), want,
                                rules[idx[i]] if idx[i] >= 0 else None,
                                rules[want] if want >= 0 else None)
        if want >= 0:
            assert level[i] == oracle.match_level(h, rules[want])
    # the same batch written into its pad bucket: the real rows' verdicts
    # are the unpadded ones, a pad row has no probe and matches nothing
    cap = pad_batch(len(hints) + 1)
    pidx, plevel = H.hint_hash_match(
        tab.arrays, H.encode_hint_queries(hints, tab, pad_to=cap))
    pidx, plevel = np.asarray(pidx), np.asarray(plevel)
    assert pidx.shape == (cap,)
    assert np.array_equal(pidx[:len(hints)], idx)
    assert np.array_equal(plevel[:len(hints)], level)
    assert (pidx[len(hints):] == -1).all()


# ---- the vectorized encoder against the per-hint form it replaced ----
#
# A plain copy of the encoder as it was before the batch became arrays
# at its first step: a Python walk that fills the byte windows hint by
# hint, one rolling-FNV pass a salt over the whole window, a stable
# argsort to compact the probes, and the engine's array-level padding.
# The served encoder must give these arrays, bit for bit.

_REF_PAD = {"hp_len": -1, "hp_slot1": -1, "hp_slot2": -1,
            "up_len": -1, "up_slot1": -1, "up_slot2": -1}


def _ref_rolling_fnv64(qbytes, salt):
    b, l = qbytes.shape
    out = np.empty((b, l + 1), dtype=np.uint64)
    h = np.full(b, H.CK.FNV64_OFFSET ^ np.uint64(salt), dtype=np.uint64)
    out[:, 0] = h
    with np.errstate(over="ignore"):
        for p in range(l):
            h = (h ^ qbytes[:, p].astype(np.uint64)) * H.CK.FNV64_PRIME
            out[:, p + 1] = h
    return out


def _ref_encode(hints, tab, pad_to=0):
    b, W, uw = len(hints), tab.hw, tab.uw
    q_hostb = np.zeros((b, W), np.uint8)
    q_hlen = np.zeros(b, np.int32)
    q_has_host = np.zeros(b, bool)
    q_urib = np.zeros((b, uw), np.uint8)
    q_ulen = np.zeros(b, np.int32)
    q_has_uri = np.zeros(b, bool)
    q_port = np.zeros(b, np.int32)
    for i, h in enumerate(hints):
        if h.host is not None:
            hb = h.host.encode()[::-1]
            q_hlen[i] = min(len(hb), 1 << 20)
            q_hostb[i, : min(len(hb), W)] = np.frombuffer(hb[:W], np.uint8)
            q_has_host[i] = True
        if h.uri is not None:
            ub = h.uri.encode()
            q_ulen[i] = min(len(ub), 1 << 20)
            q_urib[i, : min(len(ub), uw)] = np.frombuffer(ub[:uw], np.uint8)
            q_has_uri[i] = True
        q_port[i] = h.port
    h1 = _ref_rolling_fnv64(q_hostb[:, : W - 1], tab.host_salts[0])
    h2 = _ref_rolling_fnv64(q_hostb[:, : W - 1], tab.host_salts[1])
    pos = np.arange(W)[None, :]
    probe_ok = np.concatenate([
        (q_hostb == H.DOT) & (pos < q_hlen[:, None]) & (pos >= 1),
        (q_has_host & (q_hlen <= W - 1))[:, None],
    ], axis=1) & q_has_host[:, None]
    probe_len = np.concatenate([
        np.broadcast_to(pos, (b, W)), q_hlen[:, None]], axis=1
    ).astype(np.int32)
    need = int(probe_ok.sum(axis=1).max(initial=0))
    maxp = next((t for t in H.MAXP_TIERS if t >= need), H.MAXP_TIERS[-1])
    order = np.argsort(~probe_ok, axis=1, kind="stable")[:, :maxp]
    pv = np.take_along_axis(probe_ok, order, 1)
    pl = np.where(pv, np.take_along_axis(probe_len, order, 1), 0)
    mask = np.uint64(tab.host_cap - 1)

    def slots(hh, at, ok, m):
        return np.where(
            ok, (np.take_along_axis(hh, at, 1) & m).astype(np.int32), -1)

    lset = np.full(tab.caps["lset"], -1, np.int32)
    lset[: len(tab.lset)] = tab.lset
    u1 = _ref_rolling_fnv64(q_urib, tab.uri_salts[0])
    u2 = _ref_rolling_fnv64(q_urib, tab.uri_salts[1])
    lv = (lset[None, :] >= 0) & (lset[None, :] <= q_ulen[:, None]) & \
        q_has_uri[:, None]
    ll = np.where(lv, np.maximum(lset[None, :], 0), 0)
    umask = np.uint64(tab.uri_cap - 1)
    q = {
        "hostb": q_hostb, "hlen": q_hlen, "has_host": q_has_host,
        "urib": q_urib, "ulen": q_ulen, "has_uri": q_has_uri,
        "port": q_port,
        "hp_len": np.where(pv, pl, -1).astype(np.int32),
        "hp_slot1": slots(h1, pl, pv, mask),
        "hp_slot2": slots(h2, pl, pv, mask),
        "up_len": np.where(lv, ll, -1).astype(np.int32),
        "up_slot1": slots(u1, ll, lv, umask),
        "up_slot2": slots(u2, ll, lv, umask),
    }
    if pad_to > b:  # the engine's _pad_hint_q with _PAD_CUCKOO
        q = {k: np.concatenate([v, np.full((pad_to - b,) + v.shape[1:],
                                           _REF_PAD.get(k, 0), v.dtype)])
             for k, v in q.items()}
    return q


def _assert_same_arrays(got, want):
    assert set(got) == set(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        assert got[k].shape == want[k].shape, (k, got[k].shape,
                                               want[k].shape)
        assert np.array_equal(got[k], want[k]), k


def _encode_table(hw):
    """A table whose host window is `hw` (16: a 15-byte rule host; 65:
    a 64-byte one) and whose uris give a length set with gaps."""
    top = "b" * (hw - 1 - 4) + ".com" if hw == 16 else \
        "a" * 31 + "." + "b" * 32
    rules = [HintRule(host=top), HintRule(host="a.com", uri="/x"),
             HintRule(host="a.com", uri="/xy/z"), HintRule(uri="/static"),
             HintRule(uri=""), HintRule(host="*", uri="/w"),
             HintRule(host="com", port=443), HintRule(uri="*")]
    tab = H.compile_hint_hash(rules)
    assert tab.hw == hw and len(tab.lset) >= 4
    return tab, top


def _encode_corpus(kind, n, tab, top):
    """n seeded hints. `mixed` holds every edge the fill and the probe
    walk have; the other two are batches whose hash walk is cut to
    nothing (no host at all) or to one column (1-byte hosts)."""
    r = random.Random(f"{kind}/{n}/{tab.hw}")
    uris = [None, "", "/", "/x", "/xy/z/more", "/static/éé",
            "/w\x00w", "/" + "u" * (tab.uw + 3), "*"]
    if kind == "uri-only":
        return [Hint(uri=r.choice(uris), port=r.choice([0, 443]))
                for _ in range(n)]
    if kind == "one-byte-hosts":
        return [Hint(host=r.choice(["a", ".", "\x00", "", None]),
                     uri=r.choice(uris[:4])) for _ in range(n)]
    hw = tab.hw
    edge = []
    for length in range(hw - 2, hw + 3):
        # as long as the window, one less, one more...: where the host
        # reaches reversed position hw-1, that byte is a dot
        if length >= hw:
            edge.append("x" * (length - hw) + "." + top)
        else:
            edge.append(top[hw - 1 - length:])
    fixed = edge + [
        None, "", top, "q" + top, ".a.com", "a.com.", "a..com", ".", "..",
        "a\x00b.com", "\x00", "*", "q.*",
        # multi-byte utf-8, cut by the window edge at every phase
        "é" * hw, "x" + "é" * hw, "中" * hw + ".com",
        "😀" * (hw // 2) + ".a.com", "é.a.com",
        "x" * 300 + ".com", ".".join("ab" for _ in range(40)),
    ]
    out = []
    for i in range(n):
        host = fixed[i] if i < len(fixed) else (
            rand_domain() if r.random() < 0.8 else r.choice(fixed))
        out.append(Hint(host=host, port=r.choice([0, 80, 443]),
                        uri=r.choice(uris)))
    r.shuffle(out)
    return out


@pytest.mark.parametrize("padded", [False, True], ids=["bare", "padded"])
@pytest.mark.parametrize("n", [29, 96, 1500])
@pytest.mark.parametrize("kind", ["mixed", "uri-only", "one-byte-hosts"])
@pytest.mark.parametrize("hw", [16, 65])
def test_encode_hint_queries_equals_per_hint_form(hw, kind, n, padded):
    tab, top = _encode_table(hw)
    hints = _encode_corpus(kind, n, tab, top)
    assert n > H.SMALL_ENCODE  # the vectorized path is the one under test
    cap = pad_batch(n + 1) if padded else 0
    _assert_same_arrays(H.encode_hint_queries(hints, tab, pad_to=cap),
                        _ref_encode(hints, tab, pad_to=cap))


@pytest.mark.parametrize("n", [29, 96])
@pytest.mark.parametrize("kind", ["mixed", "uri-only", "one-byte-hosts"])
@pytest.mark.parametrize("hw", [16, 65])
def test_small_encoder_equals_vectorized(hw, kind, n):
    """_encode_hint_queries_small says "bit-identical": held here, on
    the corpus above, bare and padded."""
    tab, top = _encode_table(hw)
    hints = _encode_corpus(kind, n, tab, top)
    for cap in (n, pad_batch(n + 1)):
        _assert_same_arrays(H._encode_hint_queries_small(hints, tab, cap),
                            H.encode_hint_queries(hints, tab, pad_to=cap))


def test_hint_hash_parity_random():
    rules = [rand_hint_rule() for _ in range(300)]
    hints = [rand_hint() for _ in range(600)]
    for i in range(0, 200, 3):
        r = rules[i % len(rules)]
        if r.host and r.host != "*":
            hints[i] = Hint(host=r.host, port=r.port or 0, uri=r.uri)
    check_hints(rules, hints)


def test_hint_hash_shared_keys_and_tiebreak():
    # many rules share one host: bucket must be scored, earliest index
    # wins ties; later-but-higher-level must beat earlier-lower
    rules = [
        HintRule(host="a.com", uri="/x"),
        HintRule(host="a.com", uri="/xy"),
        HintRule(host="a.com"),
        HintRule(host="a.com", port=443),
        HintRule(host="a.com", uri="/xy"),  # dup of 1 — index 1 wins
        HintRule(host="com"),  # suffix for *.com
        HintRule(host="*", uri="/x"),
        HintRule(uri="/xy"),  # uri-only rule
        HintRule(uri="*"),
    ]
    hints = [
        Hint(host="a.com", uri="/xyz"),
        Hint(host="a.com", uri="/xy"),
        Hint(host="a.com"),
        Hint(host="a.com", port=443),
        Hint(host="a.com", port=8080),
        Hint(host="b.a.com", uri="/x"),
        Hint(host="z.com"),
        Hint(uri="/xyq"),
        Hint(uri="/zzz"),
        Hint(host="*"),           # exact match on the wildcard key
        Hint(host="q.*"),         # suffix match on the wildcard key
        Hint(uri="*"),            # exact uri match on wildcard uri key
    ]
    check_hints(rules, hints)


def test_hint_hash_no_host_rules_and_empty():
    rules = [HintRule(port=443), HintRule(uri="/a"), HintRule(host="h.io")]
    hints = [Hint(port=443), Hint(host="h.io", port=443), Hint(uri="/a/b"),
             Hint(host="x.h.io", uri="/a")]
    check_hints(rules, hints)


def test_hint_hash_long_host_boundaries():
    # 64-byte rule host: exact + suffix probes at the window edge
    h64 = "a" * 31 + "." + "b" * 32  # len 64
    rules = [HintRule(host=h64), HintRule(host="b" * 32)]
    hints = [Hint(host=h64), Hint(host="x." + h64), Hint(host="q" + h64)]
    check_hints(rules, hints)


def _route_nets():
    rt = RouteTable()
    for i in range(200):
        ml = rnd.choice([0, 8, 12, 16, 24, 32])
        ip = bytes([10 + i % 5, rnd.randint(0, 255), rnd.randint(0, 255), 0])
        m = mask_bytes(ml)
        net = Network(bytes(np.frombuffer(ip, np.uint8) &
                            np.frombuffer(m, np.uint8)), m)
        try:
            rt.add(RouteRule(f"r{i}", net))
        except ValueError:
            continue
    return [r.rule for r in rt.rules]


def test_cidr_hash_route_parity():
    nets = _route_nets()
    tab = H.compile_cidr_hash(nets)
    addrs = [bytes([10 + rnd.randint(0, 6), rnd.randint(0, 255),
                    rnd.randint(0, 255), rnd.randint(0, 255)])
             for _ in range(400)]
    a16, fam = T.encode_ips(addrs)
    got = np.asarray(H.cidr_hash_match(tab.arrays, a16, fam, None))
    for i, a in enumerate(addrs):
        want = next((j for j, n in enumerate(nets) if n.contains_ip(a)), -1)
        assert got[i] == want, (i, a.hex(), int(got[i]), want)


def test_cidr_hash_acl_port_buckets():
    # same network, same proto, different port ranges + allow flags:
    # one hash bucket, ordered first-match must pick by port
    net = Network(parse_ip("10.1.0.0"), mask_bytes(16))
    acl = [
        AclRule("a", net, Proto.TCP, 80, 80, False),
        AclRule("b", net, Proto.TCP, 0, 1000, True),
        AclRule("c", net, Proto.TCP, 0, 65535, False),
        AclRule("d", Network(parse_ip("0.0.0.0"), mask_bytes(0)),
                Proto.TCP, 0, 65535, True),
    ]
    nets = [r.network for r in acl]
    tab = H.compile_cidr_hash(nets, acl=acl)
    addrs = [parse_ip("10.1.2.3")] * 4 + [parse_ip("9.9.9.9")]
    ports = np.asarray([80, 443, 2000, 65535, 80], np.int32)
    a16, fam = T.encode_ips(addrs)
    got = np.asarray(H.cidr_hash_match(tab.arrays, a16, fam, ports))
    for i in range(len(addrs)):
        want = oracle.acl_first_match(acl, Proto.TCP, addrs[i], int(ports[i]))
        assert got[i] == want, (i, int(got[i]), want)


def test_cidr_hash_mixed_families():
    # v4 rule must match plain-v4, ::v4 and ::ffff:v4 queries; v6 /28
    # (4-byte-mask case) compares only the first 4 bytes
    v4net = Network(parse_ip("192.168.0.0"), mask_bytes(16))
    v6net = Network(parse_ip("fd00::"), mask_bytes(8))
    nets = [v4net, v6net]
    tab = H.compile_cidr_hash(nets)
    addrs = [parse_ip("192.168.3.4"),
             parse_ip("::192.168.3.4"),
             parse_ip("::ffff:192.168.3.4"),
             parse_ip("fd00::1"),
             parse_ip("192.169.0.1")]
    a16, fam = T.encode_ips(addrs)
    got = np.asarray(H.cidr_hash_match(tab.arrays, a16, fam, None))
    for i, a in enumerate(addrs):
        want = next((j for j, n in enumerate(nets) if n.contains_ip(a)), -1)
        assert got[i] == want, (i, int(got[i]), want)


# ------------------------------------------- the slot row carries its bucket

FAT = Network(parse_ip("10.1.0.0"), mask_bytes(16))


def _ranged_acl(ranges: int, others: int = 24):
    """`ranges` disjoint port ranges [100 i, 100 i + 50] on one network
    (one bucket), behind `others` single-range /24s."""
    acl = [AclRule(f"o{i}", Network(bytes([10, 2, i, 0]), mask_bytes(24)),
                   Proto.TCP, 0, 65535, i % 2 == 0) for i in range(others)]
    acl += [AclRule(f"f{i}", FAT, Proto.TCP, 100 * i, 100 * i + 50, True)
            for i in range(ranges)]
    return acl


def _check_against_oracle(m: CidrMatcher, addrs, ports):
    snap = m.snapshot()
    got = m.match(addrs, ports)
    for i, a in enumerate(addrs):
        want = m.oracle_snap(snap, a, None if ports is None else ports[i])
        assert got[i] == want, (i, a.hex(), ports and ports[i],
                                int(got[i]), want)
    return got


@pytest.mark.parametrize("ranges,width,hops", [(1, 1, 1), (3, 4, 1),
                                               (16, 16, 1), (17, 16, 2),
                                               (40, 16, 3)])
def test_cidr_bucket_row_layout_and_hops(ranges, width, hops):
    """A bucket rides in its slot's row up to BUCKET_INLINE entries and
    continues in overflow rows past that: the verdicts stay the ordered
    scan's whatever the hop count — the matching range first, last, in
    each overflow row, and a port every range misses."""
    acl = _ranged_acl(ranges)
    m = CidrMatcher([r.network for r in acl], backend="jax", acl=acl)
    st = m.bucket_stat()
    assert (st["width"], st["hops"]) == (width, hops)
    assert (st["overflow_slots"] > 0) == (hops > 1)
    a = parse_ip("10.1.7.7")
    ports = [100 * i + 25 for i in range(ranges)]       # inside range i
    ports += [100 * i + 75 for i in range(ranges)]      # between ranges
    ports += [100 * ranges + 75, 65535]                 # past the last
    addrs = [a] * len(ports) + [parse_ip("10.2.3.9"), parse_ip("10.9.9.9")]
    ports += [80, 80]
    got = _check_against_oracle(m, addrs, ports)
    assert got[ranges - 1] == len(acl) - 1      # the last range answers
    assert (got[ranges: 2 * ranges + 2] == -1).all()    # every range misses


def _nets_acl(acl):
    return [r.network for r in acl], acl


def test_cidr_fat_bucket_memory_follows_entries():
    """Overflow rows cost what the long bucket holds, not slots x the
    fattest bucket: 40 and 1,000 ranges on one network add rows of the
    inline width only (and the `b_next` column a multi-hop table has)."""
    base = H.compile_cidr_hash(*_nets_acl(_ranged_acl(16)))
    ct = base.caps["ct"]
    assert base.arrays["b_rows"].shape == (ct, 3 * 16)
    assert "b_next" not in base.arrays

    def nbytes(tab):
        return sum(v.nbytes for v in tab.arrays.values())

    for ranges in (40, 1000):
        tab = H.compile_cidr_hash(*_nets_acl(_ranged_acl(ranges)))
        rows = 3 * (-(-ranges // 16) - 1)   # the key sits in 3 groups
        ov = H._pow2(rows, 8)
        assert tab.caps == dict(base.caps, bk=-(-ranges // 16) * 16, ov=ov,
                                r_cap=tab.r_cap)
        assert tab.arrays["b_rows"].shape == (ct + ov, 3 * 16)
        # ov rows of 16 entries x 12 B, b_next, the wider shape marker,
        # `allow` of the rules themselves
        assert nbytes(tab) - nbytes(base) == \
            ov * 192 + 4 * (ct + ov) + 16 * (-(-ranges // 16) - 1) + \
            tab.r_cap - base.r_cap


def test_cidr_route_table_reads_one_entry_rows():
    nets = _route_nets()
    m = CidrMatcher(nets, backend="jax")
    assert m._caps["bk"] == 1
    assert m.bucket_stat() == {"width": 1, "hops": 1, "overflow_slots": 0,
                               "used_slots": m.bucket_stat()["used_slots"]}
    assert m.snapshot()[0]["b_rows"].shape[1] == 1
    addrs = [bytes([10 + rnd.randint(0, 6), rnd.randint(0, 255),
                    rnd.randint(0, 255), rnd.randint(0, 255)])
             for _ in range(400)]
    got = _check_against_oracle(m, addrs, None)
    assert len(set(got.tolist())) > 5   # a /0 route leaves no miss


def test_cidr_bucket_growth_retraces_and_same_caps_does_not():
    """A same-caps update serves from the jitted program it had; a
    bucket that outgrows the reused width grows the caps and is served
    right (one retrace), as any other cap growth."""
    def serve(ranges):
        acl = _ranged_acl(ranges)
        m.set_networks([r.network for r in acl], acl=acl)
        a = [parse_ip("10.1.0.9")] * 3
        _check_against_oracle(m, a, [100 * (ranges - 1), 75, 65535])
        return dict(m._caps), H.cidr_hash_jit._cache_size()

    acl = _ranged_acl(3)
    m = CidrMatcher([r.network for r in acl], backend="jax", acl=acl)
    caps3, n3 = serve(3)
    caps4, n4 = serve(4)        # 3 -> 4 ranges: inside the width of 4
    assert caps4 == caps3 and n4 == n3
    caps5, n5 = serve(5)        # outgrows it: width 8, a new program
    assert caps5["bk"] == 8 and n5 == n3 + 1
    caps40, _ = serve(40)       # past the inline width: overflow rows
    assert (caps40["bk"], caps40["ov"]) == (48, 8)
    assert m.bucket_stat()["hops"] == 3
    assert serve(36)[0] == caps40       # caps are kept, not shrunk


def test_cidr_sharded_bucket_growth_raises_caps_exceeded():
    acl = _ranged_acl(3)
    stab = H.compile_cidr_hash_sharded([r.network for r in acl], 2, acl=acl)
    caps = dict(stab.shards[0].caps)
    acl = _ranged_acl(4)
    same = H.compile_cidr_hash_sharded([r.network for r in acl], 2, acl=acl,
                                       caps=caps)
    assert same.shards[0].caps == caps
    for ranges in (5, 40):      # a wider row; an overflow row
        acl = _ranged_acl(ranges)
        with pytest.raises(H.CapsExceeded):
            H.compile_cidr_hash_sharded([r.network for r in acl], 2,
                                        acl=acl, caps=caps)


@pytest.mark.parametrize("ranges", [3, 400])
def test_cidr_sharded_engine_rebuilds_past_reused_caps(ranges):
    # each shard holds a slice of the rule list, so of the long bucket
    acl = _ranged_acl(2)
    m = CidrMatcher([r.network for r in acl], backend="jax-sharded", acl=acl)
    caps2 = dict(m._caps)
    acl = _ranged_acl(ranges)
    m.set_networks([r.network for r in acl], acl=acl)
    st, bk = m.bucket_stat(), m._caps["bk"]
    assert bk > caps2["bk"]
    assert (st["width"], st["hops"]) == (min(bk, 16), -(-bk // 16))
    assert (st["hops"] > 1) == (ranges == 400) == (st["overflow_slots"] > 0)
    a = [parse_ip("10.1.0.9")] * 3 + [parse_ip("10.2.5.5")]
    _check_against_oracle(m, a, [100 * (ranges - 1) + 50, 75, 0, 9])


@pytest.mark.parametrize("kind", ["route", "acl", "acl-fat"])
def test_cidr_hash_match_on_slot_rows_equals_oracle(kind):
    """cidr_hash_match on the matcher's own device arrays (slot rows
    that carry their buckets) gives the oracle's verdicts for route,
    ACL and fat-ACL tables."""
    if kind == "route":
        cm = CidrMatcher(_route_nets(), backend="jax")
        port = None
    else:
        acl = _ranged_acl(40 if kind == "acl-fat" else 5)
        cm = CidrMatcher([r.network for r in acl], backend="jax", acl=acl)
        port = np.asarray([100 * (i % 45) + 50 * (i % 2) for i in range(64)],
                          np.int32)
    snap = cm.snapshot()
    addrs = [bytes([10, 1 + i % 2, rnd.randint(0, 255), 1])
             for i in range(64)]
    a16, fam = T.encode_ips(addrs)
    got = np.asarray(H.cidr_hash_match(snap[0], a16, fam, port))
    want = [cm.oracle_snap(snap, a, None if port is None else int(port[i]))
            for i, a in enumerate(addrs)]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("ranges", [5, 40])
def test_cidr_program_gathers_one_row_a_slot(ranges):
    """The program gathers by (query, group) only — two key rows, two
    used flags, one bucket row a hop and the hop's `b_next` — and never
    over a candidate list [B, 2 G bk]; the per-rule arrays the old walk
    read (r_valid, min_port, max_port, cb_items) are not in the table."""
    import jax
    acl = _ranged_acl(ranges)
    tab = H.compile_cidr_hash([r.network for r in acl], acl=acl)
    assert not {"r_valid", "min_port", "max_port", "cb_items", "s_bs",
                "s_bc", "bk_iota"} & set(tab.arrays)
    hops, b, g = -(-ranges // 16), 32, tab.caps["g_cap"]
    a16, fam = T.encode_ips([parse_ip("10.1.0.9")] * b)
    jaxpr = jax.make_jaxpr(H.cidr_hash_match)(
        tab.arrays, a16, fam, np.zeros(b, np.int32))
    gathers = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "gather"]
    assert len(gathers) == 4 + hops + (hops - 1)
    for e in gathers:
        assert e.invars[1].aval.shape == (b, g, 1), e


def test_engine_hash_backend_update_and_growth():
    m = HintMatcher([HintRule(host="a.com")], backend="jax")
    assert m.match([Hint(host="a.com")])[0] == 0
    caps0 = dict(m._caps)
    # same-shape update: caps must be reused (no shape growth)
    m.set_rules([HintRule(host="b.com"), HintRule(host="a.com")])
    assert m.match([Hint(host="a.com")])[0] == 1
    assert m._caps["r_cap"] == caps0["r_cap"]
    # growth past capacity recompiles with bigger caps, stays correct
    rules = [HintRule(host=f"h{i}.x.io") for i in range(600)]
    m.set_rules(rules)
    got = m.match([Hint(host="h123.x.io"), Hint(host="sub.h7.x.io")])
    assert got[0] == 123 and got[1] == 7


def test_engine_cidr_hash_backend():
    nets = [Network(parse_ip("10.0.0.0"), mask_bytes(8)),
            Network(parse_ip("10.1.0.0"), mask_bytes(16))]
    m = CidrMatcher(nets, backend="jax")
    # list order wins: 10.1.x.y matches rule 0 first (insert order here)
    assert m.match([parse_ip("10.1.2.3")])[0] == 0
    assert m.match([parse_ip("11.0.0.1")])[0] == -1


def test_hash_vs_dense_vs_host_cross_check():
    rules = [rand_hint_rule() for _ in range(64)]
    hints = [rand_hint() for _ in range(128)]
    got = {}
    for be in ("jax", "jax-dense", "host"):
        got[be] = HintMatcher(rules, backend=be).match(hints)
    np.testing.assert_array_equal(got["jax"], got["host"])
    np.testing.assert_array_equal(got["jax-dense"], got["host"])


# ------------------------------------------------ stage names (metadata)

def _scope_case(kernel):
    """-> (function, args, stage names its program has to carry)."""
    from vproxy_tpu.ops import fused as F
    from vproxy_tpu.rules.maglev import MaglevMatcher, flow_slots
    hm = HintMatcher([HintRule(host=f"s{i}.example.com",
                               uri=f"/a{i}" if i % 3 == 0 else None)
                      for i in range(96)], backend="jax")
    hsnap = hm.snapshot()
    q = H.encode_hint_queries(
        [Hint(host=f"s{i}.example.com", uri="/a3/x") for i in range(16)],
        hsnap[0], pad_to=16)
    # an ACL table: a route table's rows hold no port range, so its
    # program has no cidr_gate stage
    acl = [AclRule(f"a{i}", Network(bytes([10, i, 0, 0]), mask_bytes(16)),
                   Proto.TCP, 0, 1000 * i, True) for i in range(32)]
    cm = CidrMatcher([r.network for r in acl], backend="jax", acl=acl)
    a16, fam = T.encode_ips([bytes([10, i, 1, 1]) for i in range(16)])
    port = np.arange(16, dtype=np.int32)
    hint_stages = ("hint_probe", "hint_candidates", "hint_score",
                   "hint_reduce")
    cidr_stages = ("cidr_hash", "cidr_probe", "cidr_candidates",
                   "cidr_gate", "cidr_reduce")
    if kernel == "hint_hash_match":
        return H.hint_hash_match, (hsnap[1], q), hint_stages
    if kernel == "cidr_hash_match":
        return H.cidr_hash_match, (cm.snapshot()[0], a16, fam, port), \
            cidr_stages
    if kernel == "classify_hash_all":
        cdev = cm.snapshot()[0]
        return H.classify_hash_all, (hsnap[1], cdev, cdev, q, a16, fam,
                                     port), \
            hint_stages + cidr_stages + ("route", "acl")
    mm = MaglevMatcher([(f"b{i}", 1) for i in range(5)], m=251)
    msnap = mm.snapshot()
    slots = flow_slots(len(msnap[0]),
                       [bytes([1, 2, 3, i]) for i in range(16)], None)
    return F.fused_classify_pick, (hsnap[5], q, msnap[1], slots), \
        hint_stages + ("maglev_pick",)


def _compiled_text(fn, args) -> tuple:
    """(the compiled program's text, the same without its metadata: the
    per-instruction `metadata={...}` and the file / function / location
    / stack-frame tables in the module's head)."""
    import re
    import jax
    # a fresh function object: jit's trace cache is keyed by it, and the
    # second compile of a test has to trace again
    full = jax.jit(lambda *a: fn(*a)).lower(*args).compile().as_text()
    bare = re.sub(r",? ?metadata=\{[^}]*\}", "", full)
    bare = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n.*?\n\n", "", bare, flags=re.S | re.M)
    return full, bare


@pytest.mark.parametrize("kernel", ["hint_hash_match", "cidr_hash_match",
                                    "classify_hash_all",
                                    "fused_classify_pick"])
def test_stage_scopes_are_metadata_only(kernel, monkeypatch):
    """jax.named_scope names the stages inside the kernels so a profile
    can say which stage a device op belongs to. The names must reach the
    compiled program, and it must otherwise be the very program it is
    without them (no recompile of a warm cache entry, no other op)."""
    import contextlib
    import jax
    fn, args, stages = _scope_case(kernel)
    full, bare = _compiled_text(fn, args)
    stages = [f"/{stage}/" for stage in stages]     # as in an op_name
    for stage in stages:
        assert stage in full, f"{kernel}: no op carries {stage!r}"
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    unnamed, bare_unnamed = _compiled_text(fn, args)
    assert not any(stage in unnamed for stage in stages)
    assert bare == bare_unnamed
