"""The plain reference: what every verdict has to equal.

A copy of the linear-scan semantics of the system's own oracle
(`rules/oracle.py`: Hint.matchLevel + Upstream.searchForGroup,
RouteTable.lookup, SecurityGroup.allow) and of the Maglev table build
and flow hash (Eisenbud et al., NSDI'16, with the FNV-1a hash contract
the deployment states), over the plain rule data of `gen.py`. It
imports nothing of the program and takes nothing the program made.

`hint_search` is the linear scan, one rule at a time. `HintReference`
gives the same winner for a whole pool in seconds: a rule scores 1024
or more only through its host (exact, suffix or "*"), and a rule that
scores below 1024 scores through its uri alone, so the scan is run over
the rules whose host the query can reach, and only a query that none of
them matches takes the scan over every rule, vectorised over the rule
list. `selftest.py` holds both against each other and against the
program's oracle.
"""
from __future__ import annotations

import numpy as np

HOST_SHIFT = 10
URI_MAX = 1023


# ------------------------------------------------------------------- hints

def match_level(hint: tuple, rule: tuple) -> int:
    """Hint.matchLevel: (host level << 10) + uri level, 0 = no match."""
    hhost, hport, huri = hint
    rhost, rport, ruri = rule
    if rhost is None and rport == 0 and ruri is None:
        return 0
    if hport != 0 and rport != 0 and hport != rport:
        return 0
    host_level = 0
    if rhost is not None and hhost is not None:
        if hhost == rhost:
            host_level = 3
        elif hhost.endswith("." + rhost):
            host_level = 2
        elif rhost == "*":
            host_level = 1
    uri_level = 0
    if ruri is not None and huri is not None:
        if huri == ruri:
            uri_level = len(huri) + 1
        elif huri.startswith(ruri):
            uri_level = len(ruri) + 1
        elif ruri == "*":
            uri_level = 1
        uri_level = min(uri_level, URI_MAX)
    return (host_level << HOST_SHIFT) + uri_level


def hint_search(rules: list, hint: tuple) -> int:
    """Upstream.searchForGroup: strictly greater level, earliest wins."""
    best_level, best = 0, -1
    for i, r in enumerate(rules):
        lv = match_level(hint, r)
        if lv > best_level:
            best_level, best = lv, i
    return best


class HintReference:
    def __init__(self, rules: list):
        self.rules = rules
        self.by_host: dict = {}
        for i, (h, _p, _u) in enumerate(rules):
            if h is not None:
                self.by_host.setdefault(h, []).append(i)
        # for the uri-only scan: distinct rule uris as ids
        uris = sorted({u for _h, _p, u in rules if u is not None})
        self.uri_id = {u: k for k, u in enumerate(uris)}
        self.uris = uris
        self.r_uri = np.array([self.uri_id.get(u, -1) for _h, _p, u in rules],
                              np.int32)
        self.r_port = np.array([p for _h, p, _u in rules], np.int32)

    def _host_candidates(self, host: str) -> list:
        cands = list(self.by_host.get(host, ()))
        pos = host.find(".")
        while pos != -1:
            cands += self.by_host.get(host[pos + 1:], ())
            pos = host.find(".", pos + 1)
        cands += self.by_host.get("*", ())
        return sorted(cands)

    def _uri_only(self, hint: tuple) -> int:
        """The scan over every rule for a hint no host reaches: level =
        uri level, gated by the port rule; vectorised over the rules."""
        _hh, hport, huri = hint
        if huri is None:
            return -1
        per_uri = np.zeros(len(self.uris) + 1, np.int32)  # [-1] stays 0
        for u, k in self.uri_id.items():
            if huri == u:
                per_uri[k] = len(huri) + 1
            elif huri.startswith(u):
                per_uri[k] = len(u) + 1
            elif u == "*":
                per_uri[k] = 1
        level = np.minimum(per_uri[self.r_uri], URI_MAX)
        if hport != 0:
            level = np.where((self.r_port != 0) & (self.r_port != hport),
                             0, level)
        best = int(level.max(initial=0))
        return int(np.argmax(level)) if best > 0 else -1  # first maximum

    def search(self, hint: tuple) -> int:
        best_level, best = 0, -1
        if hint[0] is not None:
            for i in self._host_candidates(hint[0]):
                lv = match_level(hint, self.rules[i])
                if lv > best_level:
                    best_level, best = lv, i
        if best_level >= (1 << HOST_SHIFT):
            return best
        # below 1024 the host gave nothing: the winner, if any, is the
        # earliest rule with the highest uri level
        return self._uri_only(hint)

    def search_all(self, hints: list) -> np.ndarray:
        return np.array([self.search(h) for h in hints], np.int32)


# -------------------------------------------------------------------- cidr

def addr_u32(a: bytes) -> int:
    return int.from_bytes(a, "big")


def cidr_first_match(nets: list, queries: list, with_port: bool,
                     block: int = 512) -> np.ndarray:
    """Index of the first entry, in table order, that contains the
    address (and, with_port, whose port range holds the port); -1 for
    none. nets: (value, masklen[, min_port, max_port]). On a route list
    in RouteTable order that is the longest prefix. with_port=False on
    an ACL table is the `noport` control: the port range ignored."""
    val = np.array([n[0] for n in nets], np.uint32)
    ml = np.array([n[1] for n in nets], np.int64)
    mask = ((0xFFFFFFFF << (32 - ml)) & 0xFFFFFFFF).astype(np.uint32)
    if with_port:
        lo = np.array([n[2] for n in nets], np.int32)
        hi = np.array([n[3] for n in nets], np.int32)
    out = np.full(len(queries), -1, np.int32)
    for s in range(0, len(queries), block):
        qs = queries[s:s + block]
        a = np.array([addr_u32(q[0]) for q in qs], np.uint32)[:, None]
        hit = (a & mask[None, :]) == val[None, :]
        if with_port:
            p = np.array([q[1] for q in qs], np.int32)[:, None]
            hit &= (lo[None, :] <= p) & (p <= hi[None, :])
        first = hit.argmax(axis=1)
        out[s:s + len(qs)] = np.where(hit.any(axis=1), first, -1)
    return out


# ------------------------------------------------------------------ maglev

FNV64_OFFSET = 0xCBF29CE484222325
FNV64_PRIME = 0x100000001B3
_MASK64 = (1 << 64) - 1


def fnv64(data: bytes) -> int:
    h = FNV64_OFFSET
    for b in data:
        h = ((h ^ b) * FNV64_PRIME) & _MASK64
    return h


def maglev_table(names: list, m: int) -> list:
    """Maglev permutation fill for equal weights: backend i prefers
    slots offset_i + k * skip_i (mod m) and the backends take turns."""
    n = len(names)
    tab = [-1] * m
    nxt = [fnv64(b"o:" + s.encode()) % m for s in names]
    skip = [fnv64(b"s:" + s.encode()) % (m - 1) + 1 for s in names]
    filled = 0
    while filled < m:
        for i in range(n):
            sl = nxt[i]
            while tab[sl] >= 0:
                sl = (sl + skip[i]) % m
            tab[sl] = i
            nxt[i] = (sl + skip[i]) % m
            filled += 1
            if filled == m:
                break
    return tab


def maglev_pick(tab: list, ip: bytes, port: int) -> int:
    """slot = FNV-1a 64 over the address bytes and the port, big-endian."""
    return tab[fnv64(ip + bytes((port >> 8 & 0xFF, port & 0xFF))) % len(tab)]
