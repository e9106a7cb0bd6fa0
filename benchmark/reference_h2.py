"""The plain reference of an HTTP/2 LB whose every stream is routed by
its `:authority`: Upstream.next — searchForGroup, then that
ServerGroup's `next`, else the upstream's weighted round robin over its
groups of nonzero weight.

The upstream's groups carry several Host rules each; a group of weight
0 is reached through its rules alone, and one group of weight 10 with
no rule is the whole round robin (Upstream.java:68-116 with a single
entry: every turn lands on it). A stream is (host, port, uri, client
address). Its answer is (group, member): the group whose rule is the
first match of the published rule list (`reference.HintReference`) and
the slot `fnv64(address) mod M` of that group's Maglev table built over
its healthy members (`reference_groups`) — an index into that list. A
stream no rule matches, or whose group has no healthy member, is
answered by the default group's own pick: group `len(healthy)`. Plain
data only: this file imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

import reference as ref
import reference_groups


def published(rules: list, rule_group: list, groups: int) -> tuple:
    """Rule i names group rule_group[i] -> (rules, their groups) in the
    order the upstream publishes them: group by group, each group's
    rules in their own order."""
    by_group: list = [[] for _ in range(groups)]
    for i, g in enumerate(rule_group):
        by_group[g].append(i)
    order = [i for idx in by_group for i in idx]
    return [rules[i] for i in order], [rule_group[i] for i in order]


def answers(rules: list, rule_group: list, healthy: list,
            default_healthy: list, m: int, queries: list,
            shift: int = 0) -> np.ndarray:
    """-> int32 [n, 2] of (group, member index in that group's healthy
    list). rules / rule_group: in published order. shift: the control —
    a matched group's pick taken from the group `shift` places on, and
    answered as that group's (another group's backend)."""
    picked = reference_groups.classify_pick(rules, rule_group, healthy, m,
                                            queries, shift=shift)
    default = ref.maglev_table(default_healthy, m)
    out = np.empty((len(queries), 2), np.int32)
    for k, q in enumerate(queries):
        v, pick = int(picked[k, 0]), int(picked[k, 1])
        if v >= 0 and pick >= 0:
            out[k] = ((rule_group[v] + shift) % len(healthy), pick)
        else:
            out[k] = (len(healthy), reference_groups.address_pick(default,
                                                                  q[3]))
    return out
