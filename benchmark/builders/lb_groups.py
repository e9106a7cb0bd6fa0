"""lb-groups256: one LB node as upstream lays it out — `lb_host`'s
Host/SNI suffix rules, rule i -> server-group i mod `groups`, and ONE
MAGLEV TABLE A GROUP over that group's healthy members, served as a
`maglev.GroupedPair` over a `MaglevTableSet`: classify, then the pick
from the table of the group the matched rule names, one launch.

Query form
    cpick      (host, 0, None, client_ip4, None)   port None: method
               `source` is address affinity; the control keys a lookup
               by (host, address), so those pairs are distinct
"""
from __future__ import annotations

import time

import gen
import reference_groups
from builders.lb_host import LbHost
# the parent of the PR that brought this deployment has no pick-table
# set: it fails here, at import, at once
from vproxy_tpu.rules.maglev import (GroupedPair, MaglevTableSet,
                                     build_table)

RULE_GROUP = 4      # bytes of the rule -> group entry a lookup reads
MAX_MEMBERS = 8
WEIGHT = 10         # ServerGroup.add's default


def group_sizes(groups: int, backends: int, seed: int) -> list:
    """1..MAX_MEMBERS members a group by a seeded draw, summing to
    `backends` exactly: every group starts with one, the rest go one by
    one to a group drawn among those not yet full."""
    if not groups <= backends <= groups * MAX_MEMBERS:
        raise ValueError(f"{backends} backends do not fit {groups} groups "
                         f"of 1..{MAX_MEMBERS}")
    rs = gen.rng_for(seed, "grpsize")
    sizes = [1] * groups
    room = list(range(groups))
    for _ in range(backends - groups):
        k = int(rs.integers(0, len(room)))
        g = room[k]
        sizes[g] += 1
        if sizes[g] == MAX_MEMBERS:
            room[k] = room[-1]
            room.pop()
    return sizes


class LbGroups(LbHost):
    # the pick taken from the next group's table (another group's
    # backend): what one table shared between the groups gives
    controls = {"cpick": "other_group"}

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        sizes = self.sizes
        groups = sizes["groups"]
        every = config["assumed"]["down_every"]
        off = int(gen.rng_for(seed, "grpdown").integers(0, every))
        self.rule_group = [i % groups for i in range(len(self.plain["hint"]))]
        # members[g]: (identity, healthy) in the group's own order;
        # identity as ServerGroup.maglev_identity spells it
        self.members, k = [], 0
        for g, n in enumerate(group_sizes(groups, sizes["backends"], seed)):
            self.members.append(
                [(f"g{g}|10.{g >> 8}.{g & 0xFF}.{b + 1}:80",
                  (k + b + off) % every != 0) for b in range(n)])
            k += n
        self.healthy = [[name for name, up in ms if up]
                        for ms in self.members]

    def install(self) -> None:
        from vproxy_tpu.rules.engine import HintMatcher
        from vproxy_tpu.rules.ir import HintRule
        ts = MaglevTableSet(m=self.sizes["maglev_m"])
        pair = GroupedPair(HintMatcher(), ts)
        t0 = time.monotonic()
        refs = [ts.alloc() for _ in self.healthy]
        for ref, names in zip(refs, self.healthy):  # one install a group
            ts.install(ref, lambda names=names: (build_table(
                [(s, WEIGHT) for s in names], ts.m), names, None)
                if names else None)
        self.install_s["maglev"] = time.monotonic() - t0
        t0 = time.monotonic()
        pair.set_rules([HintRule(host=h, port=p, uri=u)
                        for h, p, u in self.plain["hint"]],
                       payload=self.rule_group,
                       groups=[refs[g] for g in self.rule_group])
        self.install_s["hint"] = time.monotonic() - t0
        held = sum(1 for names in self.healthy if names)
        if pair.size() != len(self.plain["hint"]) or ts.size() != held:
            raise RuntimeError(f"the pair holds {pair.size()} rules and "
                               f"{ts.size()} tables, want "
                               f"{len(self.plain['hint'])} and {held}")
        self.matchers = {"cpick": pair}

    def pool_kind(self, kind: str, n: int, traffic: dict, seed: int) -> list:
        """`gen.cpick_pool`'s hosts and sources, the port left out; a
        (host, address) pair drawn twice takes the next free source."""
        rules, tag = self.plain["hint"], self.tag
        sources = traffic["client_sources"]
        rs = gen.rng_for(seed, "grppool")
        aims = rs.choice(len(rules), n, replace=n > len(rules))
        src = rs.integers(0, sources, n)
        out, seen = [], set()
        for j in range(n):
            aim = int(aims[j])
            host = gen.miss_host(aim, tag) \
                if gen._is_miss(j, traffic["miss_every"]) else rules[aim][0]
            if j % 4:
                host = "www." + host
            s = int(src[j])
            while (host, s) in seen:
                s = (s + 1) % sources
            seen.add((host, s))
            out.append((host, 0, None,
                        bytes([172, 16 + (s >> 16), (s >> 8) & 0xFF,
                               s & 0xFF]), None))
        return out

    def answers_kind(self, kind: str, queries: list, broken: bool,
                     seed: int):
        """-> int32 [n, 2]: (verdict, pick inside the verdict's group)."""
        return reference_groups.classify_pick(
            self.plain["hint"], self.rule_group, self.healthy,
            self.sizes["maglev_m"], queries, shift=int(broken))

    def work(self, kind: str, q: tuple) -> int:
        """`LbHost.work` (the hint's bytes, the client's address, one
        Maglev slot row) and the rule -> group entry: the same count
        whatever implements the set."""
        return super().work(kind, q) + RULE_GROUP


def build(config: dict, seed: int) -> LbGroups:
    return LbGroups(config, seed)
