"""The plain reference of an LB whose pick is taken inside the matched
group: Upstream.searchForGroup, then that ServerGroup's `next`.

Upstream's load balancer first finds the server-group whose annotations
match the connection's hint (`Upstream.searchForGroup`,
Upstream.java:187-198) and then asks THAT group for a backend
(`ServerGroup.next`, ServerGroup.java:422); with method `source` the
group hashes the client address over its own healthy members
(ServerGroup.java:377), here through a Maglev table a group (Eisenbud
et al., NSDI'16) built over that group's healthy members alone. A
lookup is (host, port, uri, client address); the answer is (verdict,
pick): the first-match rule of `reference.HintReference`, and the slot
`fnv64(address) mod M` of the table of the group that rule names — an
index into that group's list of healthy members. A lookup no rule
matches, a rule that names no group and a group with no healthy member
answer pick -1. Plain data only: this file imports nothing of the
program.
"""
from __future__ import annotations

import numpy as np

import reference as ref


def address_pick(tab: list, ip: bytes) -> int:
    """Source affinity: the slot of the client address alone
    (`reference.maglev_pick` always appends a port)."""
    return tab[ref.fnv64(ip) % len(tab)]


def group_tables(healthy: list, m: int) -> list:
    """healthy[g]: the identities of group g's healthy members, in the
    group's own order -> one Maglev table a group, None where a group
    has no healthy member."""
    return [ref.maglev_table(names, m) if names else None
            for names in healthy]


def classify_pick(rules: list, rule_group: list, healthy: list, m: int,
                  queries: list, shift: int = 0) -> np.ndarray:
    """-> int32 [n, 2] of (verdict, pick). rule_group[i]: the group
    rule i names, -1 for none. shift: the control — the pick is taken
    from the table of the group `shift` places on (what one table
    shared between groups gives)."""
    tables = group_tables(healthy, m)
    out = np.full((len(queries), 2), -1, np.int32)
    out[:, 0] = ref.HintReference(rules).search_all(
        [q[:3] for q in queries])
    for k, q in enumerate(queries):
        v = int(out[k, 0])
        if v < 0 or rule_group[v] < 0:
            continue
        tab = tables[(rule_group[v] + shift) % len(tables)]
        if tab is not None:
            out[k, 1] = address_pick(tab, q[3])
    return out
