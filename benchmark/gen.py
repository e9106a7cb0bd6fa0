"""Seeded rule and query generators — the benchmark's own copy.

Plain data only (tuples, bytes, ints): nothing here imports the program.
The hint rules, ACL entries and query forms are those of
`bench.north_star_rules` / `north_star_queries` (the BASELINE.json
north-star table), kept here because `bench.py` is the program's to
change. The routes are not: bench.py's 50,000 hold 2,942 copies of 13
/8s in index order, which no RouteTable accepts (it refuses a network
twice and keeps the more specific first); here they are distinct and in
the order a RouteTable holds them. What `--seed` changes: the rule names (a tag label
in every host), which rule each pooled query aims at, the addresses and
ports, and the order of the query sequence. What it never changes: the
number of rules of each form, the label count of every host (so the
encoder's probe tier), the form and kind of the query at each pool rank,
and which ranks miss — every seed runs the same work in another order.

Rule forms
    hint rule  (host, port, uri)       port 0 = any, uri None = none
    route      (value_u32, masklen)    distinct, in RouteTable order: the
                                       first containing route is the
                                       longest prefix
    acl        (value_u32, masklen, min_port, max_port)
Query forms
    hint       (host, port, uri)
    route      (addr4,)
    acl        (addr4, port)
    cpick      (host, port, uri, client_ip4, client_port)
"""
from __future__ import annotations

import numpy as np

NOPICK = -9     # second result column of a query kind that has no pick


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """One independent generator per named stream of one seed (a seed may
    exceed 2**31; SeedSequence takes any non-negative int)."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed) & (2**63 - 1), tag])))


def seed_tag(seed: int) -> str:
    """The label that makes rule names a function of the seed (fixed
    width, so host lengths and label counts do not move with it)."""
    return f"t{int(seed) & 0xFFFFFF:06x}"


def rule_host(i: int, tag: str) -> str:
    return f"svc{i}.ns{i % 997}.{tag}.example.com"


def miss_host(i: int, tag: str) -> str:
    """Same label count as a rule host, in a zone no rule names."""
    return f"svc{i}.ns{i % 997}.{tag}.nomatch.invalid"


# ------------------------------------------------------------- rule tables

def north_star_hint_rules(n: int, tag: str) -> list:
    """The 12/4/2/2-in-20 mix: plain host, host+uri prefix, host+port,
    host with wildcard uri."""
    out = []
    for i in range(n):
        r = i % 20
        h = rule_host(i, tag)
        if r < 12:
            out.append((h, 0, None))
        elif r < 16:
            out.append((h, 0, f"/api/v{i % 17}"))
        elif r < 18:
            out.append((h, 443, None))
        else:
            out.append((h, 0, "*"))
    return out


def host_suffix_rules(n: int, tag: str) -> list:
    """lb-host10k: n Host/SNI rules, each a bare domain that matches
    itself and every name under it (the reference's suffix wildcard)."""
    return [(rule_host(i, tag), 0, None) for i in range(n)]


def _v4net(i: int, masklen: int) -> tuple:
    ip = ((10 + i % 13) << 24) | (((i >> 8) & 0xFF) << 16) \
        | ((i & 0xFF) << 8) | ((i * 37) & 0xFF)
    mask = (0xFFFFFFFF << (32 - masklen)) & 0xFFFFFFFF
    return ip & mask, masklen


ROUTE_OCTETS = 13      # first octets 10..22, as bench.north_star_rules
ROUTE_LENGTHS = range(8, 25)
_STRIDE = 7919         # prime, coprime to 13 * 2**k: walks a length's
                       # prefixes once each, scattered over the octets


def route_length_counts(n: int) -> dict:
    """How many of n distinct routes each prefix length /8../24 gets: an
    even share, but no length takes more than half of the prefixes it
    has inside 10/8..22/8 (there are only 13 /8s); what the short
    lengths cannot hold goes, evenly again, to the longer ones."""
    cap = {m: max(1, ROUTE_OCTETS * 2 ** (m - 8) // 2) for m in ROUTE_LENGTHS}
    if n > sum(cap.values()):
        raise ValueError(f"{n} distinct routes do not fit /8../24 of "
                         f"{ROUTE_OCTETS} octets")
    counts = dict.fromkeys(ROUTE_LENGTHS, 0)
    left = n
    while left:
        room = [m for m in ROUTE_LENGTHS if counts[m] < cap[m]]
        share = max(1, left // len(room))
        for m in room:
            take = min(share, cap[m] - counts[m], left)
            counts[m] += take
            left -= take
    return counts


def distinct_routes(n: int) -> list:
    """n distinct v4 networks (value_u32, masklen), shortest first: the
    order an operator adds them in (aggregates before specifics)."""
    out = []
    for m, count in route_length_counts(n).items():
        space = ROUTE_OCTETS * 2 ** (m - 8)
        for k in range(count):
            p = (k * _STRIDE + m) % space
            out.append((((10 + p % ROUTE_OCTETS) << 24)
                        | ((p // ROUTE_OCTETS) << (32 - m)), m))
    return out


def net_contains(a: tuple, b: tuple) -> bool:
    """Network a holds network b (Network.contains_net)."""
    if a[1] > b[1]:
        return False
    return (b[0] >> (32 - a[1])) == (a[0] >> (32 - a[1]))


def route_table_insert(r: tuple, rules: list) -> None:
    """RouteTable.addRule (RouteTable.java:110-154; the program's copy is
    rules/ir.py RouteTable._insert), as plain code: a route goes in
    before the first listed route that holds it."""
    similar = -1
    for i, ri in enumerate(rules):
        if net_contains(ri, r) or net_contains(r, ri):
            similar = i
            break
    if similar == -1:
        rules.append(r)
        return
    insert_index = 0
    i = similar
    while i < len(rules):
        curr = rules[i]
        nxt = rules[i + 1] if i + 1 < len(rules) else None
        if net_contains(curr, r):
            insert_index = i
            break
        if net_contains(r, curr):
            if nxt is None:
                insert_index = i + 1
                break
            if net_contains(r, nxt):
                i += 1
                continue
            if net_contains(nxt, r):
                insert_index = i + 1
                break
        insert_index = i + 1
        break
    rules.insert(insert_index, r)


def route_table_order(added: list) -> list:
    """The list a RouteTable holds after `added` (distinct, shortest
    prefix first) went in one by one. Each route lands just before the
    longest route that holds it, behind that route's earlier children,
    so the list is the post-order of the prefix tree with children in
    the order they were added (selftest.py holds this against
    route_table_insert and against the program's RouteTable). Every
    route precedes every route that holds it: the first containing
    route is the longest prefix."""
    have = set(added)
    children: dict = {None: []}
    for r in added:
        v, m = r
        parent = None
        for pm in range(m - 1, 7, -1):
            cand = ((v >> (32 - pm)) << (32 - pm), pm)
            if cand in have:
                parent = cand
                break
        children.setdefault(parent, []).append(r)
    out: list = []
    stack = [(root, False) for root in reversed(children[None])]
    while stack:
        node, seen = stack.pop()
        kids = children.get(node)
        if seen or not kids:
            out.append(node)
            continue
        stack.append((node, True))
        stack += [(k, False) for k in reversed(kids)]
    return out


def north_star_routes(n: int) -> list:
    """n distinct /8../24 routes as a switch's RouteTable holds them."""
    return route_table_order(distinct_routes(n))


def north_star_acls(n: int) -> list:
    """/8../32 port-ranged ACL entries, in table order."""
    out = []
    for i in range(n):
        v, ml = _v4net(i * 3, 8 + i % 25)
        lo = (i * 7) % 60000
        out.append((v, ml, lo, lo + 1000))
    return out


def mutate_nets(nets: list, seed: int, share: float = 0.01) -> list:
    """A stale generation of a route or ACL table: `share` of the
    entries still name another network (first octet + 100, which no
    lookup asks for), at the same place in the table."""
    rs = rng_for(seed, "stalenet")
    out = list(nets)
    for i in rs.choice(len(nets), max(1, int(len(nets) * share)),
                       replace=False):
        e = out[int(i)]
        out[int(i)] = (e[0] + (100 << 24),) + tuple(e[1:])
    return out


def mutate_hint_rules(rules: list, seed: int, share: float = 0.01) -> list:
    """A stale generation: `share` of the rules carry another host. The
    control serves from this table while the comparison holds the
    published one."""
    rs = rng_for(seed, "stale")
    out = list(rules)
    for i in rs.choice(len(rules), max(1, int(len(rules) * share)),
                       replace=False):
        h, p, u = out[int(i)]
        out[int(i)] = ("old-" + h, p, u)
    return out


# ------------------------------------------------------------- query pools
#
# One generator per query kind, `<kind>_pool(n, ...)`: n distinct queries
# of that kind by their own index j. What j fixes for every seed: the
# form of the query and whether it misses (one index in `miss_every`
# asks for what no rule holds). interleave() lays the kinds of a traffic
# mix out over the pool's ranks.

def _is_miss(j: int, every: int) -> bool:
    return j % every == every - 1


def interleave(kinds: list, n: int, make) -> list:
    """-> [(kind, query)] by pool rank: rank r asks for kind
    kinds[r % len(kinds)] (a kind listed twice gets twice the ranks);
    make(kind, count) -> that kind's queries."""
    order = [kinds[r % len(kinds)] for r in range(n)]
    per = {k: iter(make(k, order.count(k))) for k in dict.fromkeys(kinds)}
    return [(k, next(per[k])) for k in order]


def hint_query(j: int, aim: int, rules: list, tag: str,
               miss_every: int) -> tuple:
    """The three forms of north_star_queries by index: exact host; a
    name under the host with a uri under the rule's prefix; host with
    port 443. A missing index asks for a host in no rule."""
    host = miss_host(aim, tag) if _is_miss(j, miss_every) else rules[aim][0]
    form = j % 3
    if form == 0:
        return (host, 0, None)
    if form == 1:
        return ("x." + host, 0, f"/api/v{aim % 17}/u")
    return (host, 443, None)


def hint_pool(n: int, rules: list, tag: str, seed: int,
              miss_every: int) -> list:
    rs = rng_for(seed, "hintpool")
    aims = rs.choice(len(rules), n, replace=n > len(rules))
    return [hint_query(j, int(aims[j]), rules, tag, miss_every)
            for j in range(n)]


def _addr_in(net: tuple, rs: np.random.Generator) -> bytes:
    """A seeded address inside one table entry's network."""
    value, masklen = net[0], net[1]
    host_bits = int(rs.integers(0, 1 << 32)) & ((1 << (32 - masklen)) - 1)
    return (value | host_bits).to_bytes(4, "big")


def cidr_pool(n: int, nets: list, seed: int, miss_every: int,
              with_port: bool) -> list:
    """n distinct lookups into one table: route lookups `(addr,)` or ACL
    lookups `(addr, port)`. Each aims at one seeded table entry (an
    address inside its network, a port inside its range; which entry
    answers is the reference's to say); a missing index asks from
    100/8..112/8, which no entry covers."""
    rs = rng_for(seed, "aclpool" if with_port else "routepool")
    out, seen = [], set()
    while len(out) < n:
        aim = nets[int(rs.integers(0, len(nets)))]
        a = _addr_in(aim, rs)
        if _is_miss(len(out), miss_every):
            a = bytes([a[0] + 90]) + a[1:]
        q = (a, int(rs.integers(aim[2], aim[3] + 1))) if with_port else (a,)
        if q not in seen:
            seen.add(q)
            out.append(q)
    return out


def cpick_pool(n: int, rules: list, tag: str, seed: int, sources: int,
               miss_every: int) -> list:
    """Accept-path queries: a name under (3 of 4 indices) or equal to a
    rule host, from one of `sources` client addresses, random source
    port."""
    rs = rng_for(seed, "cpickpool")
    aims = rs.choice(len(rules), n, replace=n > len(rules))
    src = rs.integers(0, sources, n)
    ports = rs.integers(1024, 65536, n)
    out = []
    for j in range(n):
        aim = int(aims[j])
        host = miss_host(aim, tag) if _is_miss(j, miss_every) \
            else rules[aim][0]
        if j % 4:
            host = "www." + host
        s = int(src[j])
        ip = bytes([172, 16 + (s >> 16), (s >> 8) & 0xFF, s & 0xFF])
        out.append((host, 0, None, ip, int(ports[j])))
    return out


def zipf_sequence(pool_size: int, length: int, s: float,
                  seed: int) -> np.ndarray:
    """`length` pool ranks drawn with P(rank r) proportional to
    1/(r+1)**s — the order of the run's queries."""
    p = 1.0 / np.arange(1, pool_size + 1, dtype=np.float64) ** s
    p /= p.sum()
    return rng_for(seed, "sequence").choice(
        pool_size, size=length, p=p).astype(np.int32)
