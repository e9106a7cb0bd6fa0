"""northstar-100k: one node's classify device with the BASELINE.json
north-star tables resident — Host/qname hint rules, distinct v4 routes
in RouteTable order, port-ranged ACL entries in the operator's order —
installed through the TableInstaller."""
from __future__ import annotations

import gen
import reference as ref
import work
from program import Deployment


class NorthStar(Deployment):
    kinds = ("hint", "route", "acl")
    # the guarantee each kind's control breaks: a generation in which
    # 1 % of the entries are still the old ones; the ACL port range
    # ignored
    controls = {"hint": "stale", "route": "stale", "acl": "noport"}

    def __init__(self, config: dict, seed: int):
        super().__init__()
        sizes = config["sizes"]
        self.tag = gen.seed_tag(seed)
        self._work: dict = {}
        self.plain = {
            "hint": gen.north_star_hint_rules(sizes["hint_rules"], self.tag),
            "route": gen.north_star_routes(sizes["routes"]),
            "acl": gen.north_star_acls(sizes["acls"]),
        }

    def install(self) -> None:
        self.matchers = {
            "hint": self.install_hint(self.plain["hint"]),
            "route": self.install_cidr("route", self.plain["route"], False),
            "acl": self.install_cidr("acl", self.plain["acl"], True),
        }

    def pool_kind(self, kind: str, n: int, traffic: dict, seed: int) -> list:
        if kind == "hint":
            return gen.hint_pool(n, self.plain["hint"], self.tag, seed,
                                 traffic["miss_every"])
        return gen.cidr_pool(n, self.plain[kind], seed,
                             traffic["miss_every"], kind == "acl")

    def answers_kind(self, kind: str, queries: list, broken: bool,
                     seed: int):
        table = self.plain[kind]
        if kind == "hint":
            if broken:
                table = gen.mutate_hint_rules(table, seed)
            return ref.HintReference(table).search_all(queries)
        if kind == "route":
            if broken:
                table = gen.mutate_nets(table, seed)
            return ref.cidr_first_match(table, queries, False)
        return ref.cidr_first_match(table, queries, with_port=not broken)

    def work(self, kind: str, q: tuple) -> int:
        """Bytes one lookup of this kind needs."""
        if kind == "hint":
            if "ulen" not in self._work:
                self._work["ulen"] = frozenset(
                    len(u) for _h, _p, u in self.plain["hint"]
                    if u is not None and u != "*")
            return work.hint_bytes(q, self._work["ulen"])
        if kind not in self._work:
            self._work[kind] = work.cidr_bytes(self.plain[kind],
                                               kind == "acl")
        return self._work[kind]


def build(config: dict, seed: int) -> NorthStar:
    return NorthStar(config, seed)
