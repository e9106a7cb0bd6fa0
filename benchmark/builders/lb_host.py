"""lb-host10k: one LB node — Host/SNI suffix rules whose payload maps
rule i to server-group i mod `groups`, and one Maglev table over every
backend, served as a `maglev.FusedPair` (classify + pick, one launch)."""
from __future__ import annotations

import numpy as np

import gen
import reference as ref
import work
from program import Deployment


class LbHost(Deployment):
    kinds = ("cpick",)
    controls = {"cpick": "stale"}

    def __init__(self, config: dict, seed: int):
        super().__init__()
        sizes = config["sizes"]
        self.sizes = sizes
        self.tag = gen.seed_tag(seed)
        self.plain = {"hint": gen.host_suffix_rules(sizes["hint_rules"],
                                                    self.tag)}
        per = sizes["backends"] // sizes["groups"]
        self.backend_names = [f"10.{g >> 8}.{g & 0xFF}.{b + 1}:80"
                              for g in range(sizes["groups"])
                              for b in range(per)]

    def install(self) -> None:
        groups = [i % self.sizes["groups"]
                  for i in range(len(self.plain["hint"]))]
        hm = self.install_hint(self.plain["hint"], payload=groups)
        self.matchers = {"cpick": self.install_pair(
            hm, self.backend_names, self.sizes["maglev_m"])}

    def pool_kind(self, kind: str, n: int, traffic: dict, seed: int) -> list:
        return gen.cpick_pool(n, self.plain["hint"], self.tag, seed,
                              traffic["client_sources"],
                              traffic["miss_every"])

    def answers_kind(self, kind: str, queries: list, broken: bool,
                     seed: int):
        """-> int32 [n, 2]: (verdict, pick)."""
        rules = self.plain["hint"]
        if broken:
            rules = gen.mutate_hint_rules(rules, seed)
        out = np.empty((len(queries), 2), np.int32)
        out[:, 0] = ref.HintReference(rules).search_all(
            [q[:3] for q in queries])
        tab = ref.maglev_table(self.backend_names, self.sizes["maglev_m"])
        out[:, 1] = [ref.maglev_pick(tab, q[3], q[4]) for q in queries]
        return out

    def work(self, kind: str, q: tuple) -> int:
        """Bytes one classify+pick needs: the hint's, the client's
        address and port, and one Maglev slot."""
        return work.hint_bytes(q[:3], frozenset()) + len(q[3]) + 2 + work.ROW


def build(config: dict, seed: int) -> LbHost:
    return LbHost(config, seed)
