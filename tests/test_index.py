"""rules/index.py (O(probes) host-side matchers) vs the linear oracle.

The indexes serve the accept-path latency contract (every query of the
ClassifyService's auto mode), so their winner must be bit-for-bit
the oracle's — including tie-breaks (earliest index), port gating, and
the host/uri cross-coverage cases that justify the bucket pruning.
"""
import random

import numpy as np

from vproxy_tpu.ops import tables as T
from vproxy_tpu.rules import oracle
from vproxy_tpu.rules.index import CidrIndex, HintIndex
from vproxy_tpu.rules.ir import AclRule, Hint, HintRule, Proto
from vproxy_tpu.utils.ip import Network, mask_bytes, parse_ip

rnd = random.Random(991)

WORDS = ["a", "bb", "ccc", "x", "api", "web", "cdn", "img", "v2", "svc"]
TLDS = ["com", "net", "io", "local"]


def rand_domain():
    n = rnd.randint(1, 3)
    return ".".join(rnd.choice(WORDS) for _ in range(n)) + "." + \
        rnd.choice(TLDS)


def rand_uri():
    return "/" + "/".join(rnd.choice(WORDS)
                          for _ in range(rnd.randint(1, 4)))


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
    return Hint(host=host, port=rnd.choice([0, 80, 443, 8080]), uri=uri)


def test_hint_index_parity_random():
    rules = [rand_hint_rule() for _ in range(400)]
    idx = HintIndex(rules)
    hints = [rand_hint() for _ in range(800)]
    # seed guaranteed hits (exact rule hosts/uris)
    for i in range(0, 200, 3):
        r = rules[i % len(rules)]
        if r.host and r.host != "*":
            hints[i] = Hint(host=r.host, port=r.port or 0, uri=r.uri)
    for h in hints:
        assert idx.lookup(h) == oracle.search(rules, h), h


def test_hint_index_cross_coverage_cases():
    """The pruning exactness argument's corner cases: a rule pruned from
    a uri bucket must still win via its host bucket, wildcards score."""
    rules = [
        HintRule(host="a.com", uri="/x"),
        HintRule(host="b.com", uri="/x"),   # pruned from uri bucket "/x"
        HintRule(host="a.com"),
        HintRule(host="com"),               # suffix target
        HintRule(host="*", uri="/y"),
        HintRule(uri="*"),
        HintRule(uri="/xy"),
        HintRule(host="b.com", uri="/x", port=443),
        HintRule(port=443),                 # port-only: never matches
    ]
    idx = HintIndex(rules)
    hints = [
        Hint(host="b.com", uri="/x"),       # rule 1 via host bucket
        Hint(host="b.com", uri="/x", port=443),
        Hint(host="z.a.com", uri="/x/q"),
        Hint(host="q.com"),
        Hint(host="nope.io", uri="/y/z"),
        Hint(uri="/xyz"),
        Hint(uri="/zzz"),
        Hint(host="*"),
        Hint(host="x.*"),
        Hint(port=443),
        Hint(host="com"),
    ]
    for h in hints:
        assert idx.lookup(h) == oracle.search(rules, h), h


def test_hint_index_empty_and_update_shapes():
    assert HintIndex([]).lookup(Hint(host="a.b")) == -1
    idx = HintIndex([HintRule(host="a.b")])
    assert idx.lookup(Hint()) == -1
    assert idx.lookup(Hint(host="a.b")) == 0
    assert idx.lookup(Hint(host="x.a.b")) == 0


def _scan(nets, acl, addr, port):
    for j, net in enumerate(nets):
        if net.contains_ip(addr) and (
                port is None or acl is None or
                (acl[j].min_port <= port <= acl[j].max_port)):
            return j
    return -1


def test_cidr_index_route_parity():
    nets = []
    for i in range(300):
        ml = rnd.choice([0, 8, 12, 16, 24, 32])
        ip = bytes([10 + i % 5, rnd.randint(0, 255), rnd.randint(0, 255), 0])
        m = mask_bytes(ml)
        nets.append(Network(bytes(np.frombuffer(ip, np.uint8) &
                                  np.frombuffer(m, np.uint8)), m))
    idx = CidrIndex(nets)
    for _ in range(600):
        a = bytes([10 + rnd.randint(0, 6), rnd.randint(0, 255),
                   rnd.randint(0, 255), rnd.randint(0, 255)])
        assert idx.lookup(a) == _scan(nets, None, a, None), a.hex()


def test_cidr_index_acl_ports_and_families():
    acl = []
    for i in range(80):
        ml = rnd.choice([0, 8, 16, 24, 28, 32])
        ip = bytes([10, rnd.randint(0, 3), rnd.randint(0, 255),
                    rnd.randint(0, 255)])
        m = mask_bytes(ml)
        net = Network(bytes(np.frombuffer(ip, np.uint8) &
                            np.frombuffer(m, np.uint8)), m)
        lo = rnd.randint(0, 60000)
        hi = min(65535, lo + rnd.choice([0, 10, 5000, 65535]))
        acl.append(AclRule(f"r{i}", net, Proto.TCP, lo, hi, bool(i & 1)))
    acl.append(AclRule("v6", Network(parse_ip("fd00::"), mask_bytes(8)),
                       Proto.TCP, 0, 65535, True))
    nets = [r.network for r in acl]
    idx = CidrIndex(nets, acl=acl)
    for _ in range(400):
        a = bytes([10, rnd.randint(0, 4), rnd.randint(0, 255),
                   rnd.randint(0, 255)])
        p = rnd.randint(0, 65535)
        assert idx.lookup(a, p) == _scan(nets, acl, a, p), (a.hex(), p)
    # v4-mapped and native v6 queries
    for a in (parse_ip("::ffff:10.1.2.3"), parse_ip("fd00::1"),
              parse_ip("::10.1.2.3")):
        assert idx.lookup(a, 80) == _scan(nets, acl, a, 80)
    # port=None skips the gate entirely (route-style callers)
    a = bytes([10, 0, 1, 2])
    assert idx.lookup(a, None) == _scan(nets, None if acl is None else acl,
                                        a, None)


def test_matcher_index_snap_agrees_with_oracle_snap():
    from vproxy_tpu.rules.engine import CidrMatcher, HintMatcher
    rules = [rand_hint_rule() for _ in range(200)]
    m = HintMatcher(rules, backend="jax-fp")
    snap = m.snapshot()
    for _ in range(200):
        h = rand_hint()
        assert m.index_snap(snap, h) == m.oracle_snap(snap, h), h
    nets = [Network(parse_ip(f"10.{i % 250}.{i // 250}.0"), mask_bytes(24))
            for i in range(300)]
    cm = CidrMatcher(nets, backend="jax-fp")
    csnap = cm.snapshot()
    for i in range(310):
        a = bytes([10, i % 250, i // 250, 1])
        assert cm.index_snap(csnap, a) == cm.oracle_snap(csnap, a)
