"""mesh-200k: one k8s mesh gateway node that runs tcp-lb, dns-server
and switch in one process — the north-star tables (DNS qname hint
rules, routes, ACL entries) and an LB node's Host/SNI table with its
Maglev pair, all resident on the one classify device and all served by
one ClassifyService. The three north-star kinds are `north_star`'s; the
`cpick` kind is `lb_host`'s, held as a part."""
from __future__ import annotations

from builders.lb_host import LbHost
from builders.north_star import NorthStar


class Mesh(NorthStar):
    kinds = NorthStar.kinds + LbHost.kinds
    controls = {**NorthStar.controls, **LbHost.controls}

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        sizes = config["sizes"]
        # the LB table is a stated share of the DNS table, so a toy
        # override of `hint_rules` keeps both toy
        lb_rules = sizes["hint_rules"] \
            * sizes["lb_rules_per_100_hint_rules"] // 100
        self.lb = LbHost({"sizes": dict(sizes, hint_rules=lb_rules)}, seed)

    def install(self) -> None:
        super().install()
        self.lb.install()
        self.matchers.update(self.lb.matchers)
        self.lb.matchers = {}       # run.py drops the tables from `matchers`
        self.install_s.update({"lb_" + k: v
                               for k, v in self.lb.install_s.items()})

    def pool_kind(self, kind: str, n: int, traffic: dict, seed: int) -> list:
        if kind in LbHost.kinds:
            return self.lb.pool_kind(kind, n, traffic, seed)
        return super().pool_kind(kind, n, traffic, seed)

    def answers_kind(self, kind: str, queries: list, broken: bool,
                     seed: int):
        if kind in LbHost.kinds:
            return self.lb.answers_kind(kind, queries, broken, seed)
        return super().answers_kind(kind, queries, broken, seed)

    def work(self, kind: str, q: tuple) -> int:
        if kind in LbHost.kinds:
            return self.lb.work(kind, q)
        return super().work(kind, q)


def build(config: dict, seed: int) -> Mesh:
    return Mesh(config, seed)
