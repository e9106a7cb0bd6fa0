"""One packed query buffer a launch (ops/hashmatch: arena_layout,
QueryArena, unpack_arena; the five served entries of the "jax" backend).

(a) The layout against this file's own plain copy of the field lists:
offsets, the numpy views of a filled arena, the device's unpack of it;
the encoders' arenas byte for byte against their own columns packed the
plain way, the small hint encoder's against the vectorized one's, the
cidr and slot columns against the arena-free forms the engine had.
(b) Each of the five packed programs answers a seeded batch at its pad
bucket as rules/oracle.py and the host planes do, pad rows included.
(c) No layout adds a program shape: over the same seeded batches a
packed entry compiles as many programs as its dict form.
"""
import math

import jax
import numpy as np
import pytest

from vproxy_tpu.ops import fused as F
from vproxy_tpu.ops import hashmatch as H
from vproxy_tpu.ops import tables as T
from vproxy_tpu.rules import engine as E
from vproxy_tpu.rules import maglev as MG
from vproxy_tpu.rules import oracle
from vproxy_tpu.rules.ir import AclRule, Hint, HintRule, Proto
from vproxy_tpu.utils.ip import Network, mask_bytes, parse_ip

BUCKETS = (8, 32, 64, 256, 2048)
M = 251


# ------------------------------------------- the plain copy of the layouts

def plain_hint_fields(cap, hw, uw, maxp, lw, slots):
    """(name, dtype, shape) in the order the columns lie in a hint
    batch's arena: words, bytes, the host-probe block last."""
    f = [("hlen", np.int32, (cap,)), ("ulen", np.int32, (cap,)),
         ("port", np.int32, (cap,)), ("up_len", np.int32, (cap, lw)),
         ("up_slots", np.int32, (2, cap, lw))]
    if slots:
        f.append(("slots", np.int32, (cap,)))
    return f + [("has_host", np.bool_, (cap,)), ("has_uri", np.bool_, (cap,)),
                ("hostb", np.uint8, (cap, hw)), ("urib", np.uint8, (cap, uw)),
                ("hp_len", np.int32, (cap, maxp)),
                ("hp_slots", np.int32, (2, cap, maxp))]


def plain_cidr_fields(cap, gated, tid):
    f = [("fam", np.int32, (cap,))]
    if gated:
        f.append(("port", np.int32, (cap,)))
    if tid:
        f.append(("tid", np.int32, (cap,)))
    return f + [("a16", np.uint8, (cap, 16))]


def plain_offsets(fields):
    """[(name, dtype, shape, byte offset)], bytes in all: a column starts
    on a word, a byte column is padded up to one."""
    out, at = [], 0
    for name, dt, shape in fields:
        out.append((name, dt, shape, at))
        n = math.prod(shape) * np.dtype(dt).itemsize
        at += n + (-n % 4)
    return out, at


def plain_pack(cols, fields):
    """The columns' bytes laid end to end the plain way, zero padded to
    whole words -> int32 words."""
    out = b""
    for name, dt, shape in fields:
        col = np.ascontiguousarray(cols[name])
        assert col.dtype == dt and col.shape == shape, name
        out += col.tobytes() + b"\0" * (-col.nbytes % 4)
    return np.frombuffer(out, np.int32)


HINT_SHAPES = [(cap, 16, 8, 5, 4, False) for cap in BUCKETS] \
    + [(8, 65, 128, t, 8, True) for t in H.MAXP_TIERS] \
    + [(3, 17, 9, 7, 4, True), (2048, 64, 32, 9, 16, True)]
CIDR_SHAPES = [(cap, gated, tid) for cap in (3, 8, 512, 2048)
               for gated, tid in ((False, False), (True, False),
                                  (False, True), (True, True))]


def _check_layout(layout, fields):
    want, nbytes = plain_offsets(fields)
    assert layout.words * 4 == nbytes
    assert [(n, np.dtype(d), s, 4 * o) for n, o, d, s in layout.fields] \
        == [(n, np.dtype(d), s, o) for n, d, s, o in want]
    # a filled arena: the numpy views and the device's unpack are the
    # plain slices of its bytes
    rs = np.random.default_rng(layout.words)
    arena = rs.integers(-2**31, 2**31, layout.words, dtype=np.int64) \
        .astype(np.int32)
    raw = arena.tobytes()
    views = H.arena_views(arena, layout.fields)
    dev = jax.jit(H.unpack_arena, static_argnames="layout")(arena, layout)
    assert list(views) == sorted(dev, key=list(views).index) \
        == [n for n, *_ in want]
    for name, dt, shape, at in want:
        plain = np.frombuffer(raw, np.uint8 if dt == np.bool_ else dt,
                              math.prod(shape), at).reshape(shape)
        if dt == np.bool_:
            plain = plain != 0
        assert views[name].dtype == dt and views[name].shape == shape
        assert np.shares_memory(views[name], arena)
        got = views[name] if dt != np.bool_ \
            else views[name].view(np.uint8) != 0
        assert np.array_equal(got, plain), name
        assert np.asarray(dev[name]).dtype == dt
        assert np.array_equal(np.asarray(dev[name]), plain), name


@pytest.mark.parametrize("shape", HINT_SHAPES, ids=lambda s: "-".join(
    str(int(v)) for v in s))
def test_hint_layout_is_the_plain_field_list(shape):
    cap, hw, uw, maxp, lw, slots = shape
    _check_layout(H.hint_layout(cap, hw, uw, maxp, lw, slots=slots),
                  plain_hint_fields(*shape))
    # everything before the host-probe block lies where it does at
    # every tier (the encoders write it before the tier is known)
    other = H.hint_layout(cap, hw, uw, H.MAXP_TIERS[-1], lw, slots=slots)
    assert other.fields[:-2] == H.hint_layout(
        cap, hw, uw, maxp, lw, slots=slots).fields[:-2]


@pytest.mark.parametrize("shape", CIDR_SHAPES, ids=lambda s: "-".join(
    str(int(v)) for v in s))
def test_cidr_layout_is_the_plain_field_list(shape):
    _check_layout(H.cidr_layout(*shape), plain_cidr_fields(*shape))


# ------------------------------------------------- the encoders' arenas

def _hint_table(uri: bool, hw65: bool = True):
    rules = [HintRule(host=f"s{i}.example.com",
                      uri=f"/a{i % 7}/b" if uri and i % 3 == 0 else None,
                      port=80 if i % 11 == 0 else 0) for i in range(96)]
    if hw65:
        rules.append(HintRule(host="x" * 64))       # hw 65: every tier
    if uri:
        rules.append(HintRule(uri="/only/uri"))
    return rules, H.compile_hint_hash(rules)


def _hosts_of_tier(tier: int, n: int) -> list:
    """n hosts whose fattest row needs exactly `tier`'s worth of probes
    in a 65-byte window: d dots -> d + 1 probes while the host fits."""
    d = {5: 4, 7: 6, 9: 8, 17: 16}.get(tier)
    if d is not None:
        deep = ".".join("a" * (1 + (k == 0)) for k in range(d + 1))
    else:   # 33: 32 dots of a 65-byte host; 66: a window of dots
        deep = ".".join("a" for _ in range(33)) if tier == 33 else "." * 80
    return [deep if i == n // 2 else f"w{i}.s{i % 96}.example.com"
            for i in range(n)]


def _cols_of(q):
    cols = dict(q)
    cols["hp_slots"] = np.stack([q["hp_slot1"], q["hp_slot2"]])
    cols["up_slots"] = np.stack([q["up_slot1"], q["up_slot2"]])
    if q.slots is not None:
        cols["slots"] = q.slots
    return cols


@pytest.mark.parametrize("uri", [False, True], ids=["host-rules", "uri-rules"])
@pytest.mark.parametrize("n,cap", [(5, 8), (20, 32), (40, 64), (150, 256),
                                   (1500, 2048)])
@pytest.mark.parametrize("tier", H.MAXP_TIERS)
def test_hint_encoders_fill_one_arena(tier, n, cap, uri):
    rules, tab = _hint_table(uri)
    assert tab.hw == 65
    hints = [Hint(host=h, port=80 if i % 5 == 0 else 0,
                  uri=("/a3/b/c" if i % 2 else None) if uri else None)
             for i, h in enumerate(_hosts_of_tier(tier, n))]
    hints[0] = Hint(uri="/only/uri/x") if uri else Hint()
    slots = bool(n % 2)
    q = H.encode_hint_queries(hints, tab, pad_to=cap, slots=slots)
    lw = tab.caps["lset"]
    assert q.layout == H.hint_layout(cap, tab.hw, tab.uw, tier, lw,
                                     slots=slots)
    assert q.arena.dtype == np.int32 and q.arena.shape == (q.layout.words,)
    assert len(q) == 13 and (q.slots is not None) == slots
    for col in list(q.values()) + ([q.slots] if slots else []):
        assert np.shares_memory(col, q.arena)
    # the arena is its columns packed the plain way, byte for byte: no
    # stray byte between them
    fields = plain_hint_fields(cap, tab.hw, tab.uw, tier, lw, slots)
    assert np.array_equal(plain_pack(_cols_of(q), fields), q.arena)
    # pad rows are the arena's fill: no probe, nothing to compare
    for k, v in q.items():
        fill = -1 if k[:3] in ("hp_", "up_") else 0
        assert (v[n:] == fill).all(), k
    if slots:
        assert not q.slots.any()        # the caller's to fill
    # both encoders fill the same bytes
    if n <= H.SMALL_ENCODE:
        other = H._encode_hint_arrays(
            hints, cap, tab.hw, tab.uw, tab.host_salts, tab.host_cap,
            tab.uri_salts, tab.uri_cap, tab.lset, lw, slots).queries()
    elif n <= 64:
        other = H._encode_hint_queries_small(hints, tab, cap, slots)
    else:
        return
    assert other.layout == q.layout
    assert np.array_equal(other.arena, q.arena)


def _plain_encode_addrs(addrs, ports, pad_to):
    """(a16, fam, p) as the engine encoded a cidr batch before it had an
    arena: arrays of their own, pad rows concatenated."""
    a16, fam = T.encode_ips(addrs)
    p = None if ports is None else np.asarray(ports, np.int32)
    if pad_to and pad_to > a16.shape[0]:
        k = pad_to - a16.shape[0]
        a16 = np.concatenate([a16, np.zeros((k, 16), np.uint8)])
        fam = np.concatenate([fam, np.full(k, -1, fam.dtype)])
        if p is not None:
            p = np.concatenate([p, np.zeros(k, p.dtype)])
    return a16, fam, p


def _addrs(family: str, n: int, seed: int) -> list:
    rs = np.random.default_rng(seed)
    v4 = [bytes([10, int(a), int(b), 7])
          for a, b in rs.integers(0, 32, (n, 2))]
    v6 = [parse_ip(f"fd00:{int(a):x}::{int(b):x}")
          for a, b in rs.integers(0, 32, (n, 2))]
    return {"v4": v4, "v6": v6,
            "mixed": [v4[i] if i % 2 else v6[i] for i in range(n)]}[family]


@pytest.mark.parametrize("family", ["v4", "v6", "mixed"])
@pytest.mark.parametrize("kind", ["route", "acl", "table_set"])
@pytest.mark.parametrize("n,cap", [(5, 8), (8, 8), (300, 512),
                                   (1200, 2048), (7, 0)])
def test_cidr_encoder_fills_one_arena(n, cap, kind, family):
    addrs = _addrs(family, n, n + cap)
    ports = list(range(1000, 1000 + n)) if kind == "acl" else None
    tid = kind == "table_set"
    q = E._encode_addrs(addrs, ports, cap, n, tid=tid)
    rows = max(n, cap)
    assert q.layout == H.cidr_layout(rows, gated=ports is not None, tid=tid)
    a16, fam, p = _plain_encode_addrs(addrs, ports, cap)
    assert np.array_equal(q["a16"], a16) and np.array_equal(q["fam"], fam)
    assert q["a16"].dtype == a16.dtype and q["fam"].dtype == fam.dtype
    assert ("port" in q) == (p is not None)
    if p is not None:
        assert np.array_equal(q["port"], p) and q["port"].dtype == p.dtype
    if tid:
        assert q["tid"].dtype == np.int32 and not q["tid"].any()
    assert np.array_equal(plain_pack(q, plain_cidr_fields(
        rows, ports is not None, tid)), q.arena)
    # the plain encoder is what it was: arrays of its own at n rows
    assert T.encode_ips(addrs)[0].shape == (n, 16)


@pytest.mark.parametrize("with_ports", [False, True])
@pytest.mark.parametrize("n,cap", [(5, 8), (32, 32), (300, 512)])
def test_slot_column_is_flow_slots_then_zeros(n, cap, with_ports):
    _rules, tab = _hint_table(False)
    ips = _addrs("mixed", n, cap)
    ports = [4000 + i for i in range(n)] if with_ports else None
    q = H.encode_hint_queries([Hint(host=f"s{i}.example.com")
                               for i in range(n)], tab, pad_to=cap,
                              slots=True)
    E._fused_slots(65537, ips, ports, q.slots)
    want = MG.flow_slots(65537, ips, ports)
    assert q.slots.dtype == np.int32 and q.slots.shape == (cap,)
    assert np.array_equal(q.slots[:n], want) and not q.slots[n:].any()


def test_sharded_encoder_keeps_its_slot_blocks():
    """encode_hint_queries_sharded shares the vectorized body: S shards'
    slot blocks come out [S, cap, P] a salt, each shard's as that
    shard's own table encodes them."""
    rules = [HintRule(host=f"s{i}.example.com",
                      uri=f"/a{i % 5}" if i % 4 == 0 else None)
             for i in range(120)]
    stab = H.compile_hint_hash_sharded(rules, 4)
    hints = [Hint(host=f"w.s{i}.example.com", uri="/a3/x")
             for i in range(40)]
    q = H.encode_hint_queries_sharded(hints, stab, pad_to=64)
    for k in ("hp_slot1", "hp_slot2", "up_slot1", "up_slot2", "hostb"):
        assert q[k].shape[:2] == (4, 64), k
    for s, t in enumerate(stab.shards):
        one = H._encode_hint_arrays(
            hints, 64, t.hw, t.uw, t.host_salts, t.host_cap, t.uri_salts,
            t.uri_cap, stab.lset_u, t.caps["lset_u"]).queries()
        for k in ("hp_slot1", "hp_slot2", "up_slot1", "up_slot2", "hp_len",
                  "up_len", "hostb", "hlen"):
            assert np.array_equal(q[k][s], one[k]), (s, k)


def test_a_query_arena_is_a_dict_to_jax_and_fresh_every_batch():
    _rules, tab = _hint_table(True)
    hints = [Hint(host=f"s{i}.example.com", uri="/a3/b/c") for i in range(9)]
    q1 = H.encode_hint_queries(hints, tab, pad_to=16)
    q2 = H.encode_hint_queries(hints, tab, pad_to=16)
    assert isinstance(q1, dict) and not np.shares_memory(q1.arena, q2.arena)
    assert np.array_equal(q1.arena, q2.arena)
    leaves, tree = jax.tree_util.tree_flatten(q1)
    assert len(leaves) == 13 and type(tree.unflatten(leaves)) is dict
    # the plain kernel takes it as it takes a dict of columns, and the
    # packed entry answers as the plain kernel does
    plain = jax.jit(H.hint_hash_match)(tab.arrays, q1)
    packed = H.hint_hash_jit(tab.arrays, q1.arena, q1.layout)
    for a, b in zip(plain, packed):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_served_programs_keep_their_names():
    """The benchmark's kernel metrics find the programs by `jit_<name>`."""
    _rules, tab = _hint_table(False)
    q = H.encode_hint_queries([Hint(host="s1.example.com")], tab, pad_to=8,
                              slots=True)
    cq, sq = H.cidr_queries(8), H.cidr_queries(8, tid=True)
    ctab = H.compile_cidr_hash([Network.parse("10.0.0.0/8")])
    stack = H.stack_cidr_tables([ctab])[0]
    fd = F.pack_hint_table(tab.arrays)
    mtab = np.zeros(M, np.int32)
    col = np.full((fd["pk_meta"].shape[0], 2), -1, np.int32)
    cases = {
        "hint_hash_match": H.hint_hash_jit.lower(tab.arrays, q.arena,
                                                 q.layout),
        "cidr_hash_match": H.cidr_hash_jit.lower(ctab.arrays, cq.arena,
                                                 cq.layout),
        "cidr_set_match": H.cidr_set_jit.lower(stack, sq.arena, sq.layout),
        "fused_classify_pick": F.fused_jit.lower(fd, mtab, q.arena,
                                                 q.layout),
        "fused_group_pick": F.group_jit.lower(
            fd, col, np.zeros(8, np.int32), np.zeros((8, M), np.int8),
            q.arena, q.layout),
    }
    for name, low in cases.items():
        text = low.as_text(debug_info=True)
        assert f"module @jit_{name} " in text, name
        assert f"jit({name})/unpack/" in text, name


# ------------------------------------------------------ parity (b)

def _hint_rules(seed: int, n: int = 300) -> list:
    rs = np.random.default_rng(seed)
    out = []
    for i in range(n):
        kind = i % 10
        host = f"svc{i}.ns{i % 7}.s{seed}.example.com"
        out.append(HintRule(
            host=None if kind == 9 else host,
            uri=f"/api/v{int(rs.integers(1, 5))}" if kind in (3, 9) else None,
            port=443 if kind == 5 else 0))
    return out


def _hint_batch(seed: int, rules: list, n: int) -> list:
    rs = np.random.default_rng(seed + 100)
    out = []
    for j in range(n):
        r = rules[int(rs.integers(0, len(rules)))]
        host = r.host or f"none{j}.invalid"
        if j % 10 == 9:
            host = host.replace("example.com", "nomatch.invalid")
        if j % 3:
            host = "www." + host
        out.append(Hint(host=host, port=443 if j % 4 == 0 else 0,
                        uri=f"/api/v{j % 5}/x" if j % 2 else None))
    return out


def _nets(seed: int, n: int = 200, v6: bool = True) -> list:
    """Networks of mixed lengths inside 10/8 ... 25/8 and fd00::/16, one
    in four v6, in the order they were drawn."""
    rs = np.random.default_rng(seed)
    out = []
    for i in range(n):
        a, b, bits = int(rs.integers(0, 16)), int(rs.integers(0, 256)), \
            int(rs.choice([8, 12, 16, 20, 24]))
        if v6 and i % 4 == 3:
            ip, bits = parse_ip(f"fd00:{a:x}:{b:x}::"), 32 + bits
        else:
            ip = bytes([10 + (a if bits == 8 else 0), a * 16 + b % 16, b, 0])
        mask = mask_bytes(bits)     # 16 bytes past /32: the v6 ones
        out.append(Network(bytes(x & m for x, m in zip(ip, mask)), mask))
    return out


def _cidr_batch(seed: int, n: int) -> list:
    rs = np.random.default_rng(seed + 200)
    out = []
    for j in range(n):
        a, b = int(rs.integers(0, 16)), int(rs.integers(0, 256))
        if j % 4 == 3:
            out.append(parse_ip(f"fd00:{a:x}:{b:x}::{j + 1:x}"))
        else:
            out.append(bytes([10 + (a if j % 5 == 0 else 0),
                              a * 16 + b % 16, b, j % 250]))
    return out


def _first(nets, acl, addr, port):
    """The ordered scan: RouteTable.lookup / SecurityGroup.allow."""
    for i, net in enumerate(nets):
        if net.contains_ip(addr) and (
                acl is None or port is None
                or acl[i].min_port <= port <= acl[i].max_port):
            return i
    return -1


PARITY = [(prog, seed, n, cap) for prog in
          ("hint_hash_match", "cidr_hash_match-route",
           "cidr_hash_match-acl", "cidr_set_match", "fused_classify_pick",
           "fused_group_pick")
          for seed, n, cap in ((1, 5, 8), (2, 20, 32), (3, 150, 256))]


@pytest.mark.parametrize("prog,seed,n,cap", PARITY, ids=lambda v: str(v))
def test_packed_program_answers_as_the_oracle(prog, seed, n, cap):
    """Through the matcher's own dispatch_snap at a pad bucket: the
    packed launch, every real row against the oracle, every pad row
    answering nothing."""
    rules = _hint_rules(seed)
    hints = _hint_batch(seed, rules, n)
    addrs = _cidr_batch(seed, n)
    if prog == "hint_hash_match":
        hm = E.HintMatcher(rules, backend="jax")
        got = np.asarray(hm.dispatch_snap(hm.snapshot(), hints, pad_to=cap))
        want = [oracle.search(rules, h) for h in hints]
        assert got.shape == (cap,) and got[:n].tolist() == want
        assert (got[n:] == -1).all() and max(want) >= 0 and min(want) == -1 \
            or n < 10
        return
    if prog.startswith("cidr_hash_match"):
        nets = _nets(seed)
        acl = ports = None
        if prog.endswith("acl"):
            acl = [AclRule(f"r{i}", net, Proto.TCP, 100 * (i % 7),
                           100 * (i % 7) + 2000, i % 2 == 0)
                   for i, net in enumerate(nets)]
            ports = [(37 * j) % 3000 for j in range(n)]
        cm = E.CidrMatcher(nets, backend="jax", acl=acl)
        got = np.asarray(cm.dispatch_snap(cm.snapshot(), addrs, ports,
                                          pad_to=cap))
        want = [_first(nets, acl, a, None if ports is None else ports[j])
                for j, a in enumerate(addrs)]
        if acl is not None:
            assert want == [oracle.acl_first_match(acl, Proto.TCP, a, p)
                            for a, p in zip(addrs, ports)]
        assert got.shape == (cap,) and got[:n].tolist() == want
        assert (got[n:] == -1).all()
        return
    if prog == "cidr_set_match":
        ts = E.CidrTableSet("any", backend="jax")
        tables = [_nets(seed + k, 60 + 40 * k) for k in range(3)]
        views = [ts.view() for _ in range(4)]       # the last holds none
        for v, nets in zip(views, tables):
            v.set_networks(nets)
        asked = [j % 4 for j in range(n)]
        got = np.asarray(ts.dispatch_snap(
            ts.snapshot(), addrs, None, [views[k].key for k in asked],
            pad_to=cap))
        want = [-1 if k == 3 else _first(tables[k], None, a, None)
                for k, a in zip(asked, addrs)]
        assert got.shape == (cap,) and got[:n].tolist() == want
        assert (got[n:] == -1).all()
        return
    ips = [bytes(a[-4:]) for a in addrs]
    ports = [None if j % 3 == 0 else 1000 + j for j in range(n)]
    payloads = list(zip(hints, ips, ports))
    if prog == "fused_classify_pick":
        pair = MG.FusedPair(E.HintMatcher(rules, backend="jax"),
                            MG.MaglevMatcher([(f"b{i}", 1 + i % 3)
                                              for i in range(9)], m=M))
    else:
        ts = MG.MaglevTableSet(m=M, backend="jax")
        pair = MG.GroupedPair(E.HintMatcher(backend="jax"), ts)
        refs = [ts.alloc() for _ in range(5)]
        for g, ref in enumerate(refs[:4]):          # the last holds none
            names = [f"g{g}|b{b}" for b in range(1 + g)]
            ts.install(ref, lambda names=names: (
                MG.build_table([(s, 10) for s in names], M), names, g))
        pair.set_rules(rules, groups=[refs[i % 5] if i % 9 else -1
                                      for i in range(len(rules))])
    snap = pair.snapshot()
    l0, f0 = E.dispatch_launches_total(), E.fused_dispatches_total()
    got = np.asarray(pair.dispatch_snap(snap, payloads, pad_to=cap))
    assert E.dispatch_launches_total() - l0 == 1
    assert E.fused_dispatches_total() - f0 == 1
    want = [pair.index_snap(snap, pl) for pl in payloads]
    assert [v for v, _p in want] == [oracle.search(rules, h) for h in hints]
    assert got.shape == (cap, 2)
    assert [tuple(r) for r in got[:n].tolist()] == want
    assert (got[n:, 0] == -1).all()
    if prog == "fused_group_pick":
        assert (got[n:, 1] == -1).all()


# ------------------------------------------------- program shapes (c)

def _fresh(entry: str):
    """(the dict form, the packed form) of a served program as fresh
    jitted functions: their caches count this test's compiles alone."""
    if entry == "hint_hash_match":
        return (jax.jit(lambda t, q: H.hint_hash_match(t, q)),
                jax.jit(lambda t, buf, layout: H._hint_packed(
                    t, buf, layout), static_argnames="layout"))
    if entry in ("cidr_hash_match", "cidr_set_match"):
        match = getattr(H, entry)
        return (jax.jit(lambda t, a, f, port, tid: match(
                    t, a, f, port=port, tid=tid)),
                jax.jit(lambda t, buf, layout: H._cidr_packed(match)(
                    t, buf, layout), static_argnames="layout"))
    if entry == "fused_classify_pick":
        return (jax.jit(lambda ht, q, mtab, slots: F.fused_classify_pick(
                    ht, q, mtab, slots)),
                jax.jit(lambda ht, mtab, buf, layout: F._fused_packed(
                    ht, mtab, buf, layout), static_argnames="layout"))
    return (jax.jit(lambda ht, q, rg, ow, st, slots: F.fused_group_pick(
                ht, q, rg, ow, st, slots)),
            jax.jit(lambda ht, rg, ow, st, buf, layout: F._group_packed(
                ht, rg, ow, st, buf, layout), static_argnames="layout"))


# (rows, bucket, probe tier): batches that repeat shapes and change them
HINT_BATCHES = [(5, 8, 5), (7, 8, 5), (5, 8, 7), (20, 32, 5), (40, 64, 5),
                (30, 64, 5), (40, 64, 9), (20, 32, 5), (150, 256, 7)]


@pytest.mark.parametrize("entry", ["hint_hash_match", "fused_classify_pick",
                                   "fused_group_pick"])
def test_hint_layouts_add_no_program_shape(entry):
    rules, tab = _hint_table(True)
    dict_fn, packed_fn = _fresh(entry)
    fd = F.pack_hint_table(tab.arrays)
    mtab = np.arange(M, dtype=np.int32) % 7
    col = np.zeros((fd["pk_meta"].shape[0], 2), np.int32)
    owner, set_tab = np.zeros(8, np.int32), np.ones((8, M), np.int8)
    shapes = set()
    for k, (n, cap, tier) in enumerate(HINT_BATCHES):
        hints = [Hint(host=h, uri="/a3/b/c" if (i + k) % 2 else None)
                 for i, h in enumerate(_hosts_of_tier(tier, n))]
        slots = entry != "hint_hash_match"
        q = H.encode_hint_queries(hints, tab, pad_to=cap, slots=slots)
        shapes.add((cap, tier))
        if slots:
            E._fused_slots(M, _addrs("v4", n, k), None, q.slots)
        if entry == "hint_hash_match":
            a = dict_fn(tab.arrays, dict(q))[0]
            b = packed_fn(tab.arrays, q.arena, q.layout)[0]
        elif entry == "fused_classify_pick":
            a = dict_fn(fd, dict(q), mtab, q.slots.astype(np.int64))
            b = packed_fn(fd, mtab, q.arena, q.layout)
        else:
            a = dict_fn(fd, dict(q), col, owner, set_tab,
                        q.slots.astype(np.int64))
            b = packed_fn(fd, col, owner, set_tab, q.arena, q.layout)
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert dict_fn._cache_size() == packed_fn._cache_size() == len(shapes)


# (rows, bucket, ports compared, v6 among them)
CIDR_BATCHES = [(5, 8, False, False), (8, 8, False, True),
                (5, 8, True, False), (300, 512, True, True),
                (200, 512, True, False), (300, 512, False, False),
                (6, 8, True, True)]


@pytest.mark.parametrize("entry", ["cidr_hash_match", "cidr_set_match"])
def test_cidr_layouts_add_no_program_shape(entry):
    nets = _nets(7)
    acl = [AclRule(f"r{i}", net, Proto.TCP, 0, 50000, True)
           for i, net in enumerate(nets)]
    tab = H.compile_cidr_hash(nets, acl=acl)
    tid = entry == "cidr_set_match"
    arrays = H.stack_cidr_tables([tab, None, tab])[0] if tid else tab.arrays
    dict_fn, packed_fn = _fresh(entry)
    shapes = set()
    for k, (n, cap, gated, v6) in enumerate(CIDR_BATCHES):
        addrs = _cidr_batch(k, n) if v6 else _addrs("v4", n, k)
        q = E._encode_addrs(addrs, [80 + i for i in range(n)]
                            if gated else None, cap, n, tid=tid)
        if tid:
            q["tid"][:n] = [2 * (i % 2) for i in range(n)]
        shapes.add((cap, gated))
        a = dict_fn(arrays, np.array(q["a16"]), np.array(q["fam"]),
                    np.array(q["port"]) if gated else None,
                    np.array(q["tid"]) if tid else None)
        b = packed_fn(arrays, q.arena, q.layout)
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert (np.asarray(b)[:n] >= 0).any()
    assert dict_fn._cache_size() == packed_fn._cache_size() == len(shapes)
