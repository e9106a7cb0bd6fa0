"""switch-vpc64: one node's switch as upstream lays it out — one
RouteTable a VNI, BASELINE configs[3]'s 50,000 routes spread over the
VPCs by a 1/(i+1) share, every VPC inside the same 10/8..22/8 space
(tenants overlap) — served as ONE route-table set (the program's
`CidrTableSet`): a lookup names its VPC through the view it is
submitted on, a burst is one device batch however many VPCs it names.
The switch's one bare-VXLAN SecurityGroup is the 5,000-entry ACL
table, resident beside it as a plain matcher (kind `acl`).

Query forms
    route      (addr4, None, vpc)   the control keys a lookup by its
                                    address alone, so addresses are
                                    distinct across the pool
    acl        (addr4, port)
"""
from __future__ import annotations

import time
from functools import partial

import numpy as np

import gen
import reference as ref
import reference_vpc
import work
from program import Deployment
# the parent of the PR that brought this deployment has no table set:
# it fails here, at import, at once
from vproxy_tpu.rules.engine import CidrTableSet

TABLE_ID = 4    # bytes of the table-id row a set lookup uploads


def vpc_sizes(routes: int, vpcs: int) -> list:
    """VPC i holds a share proportional to 1/(i+1) of the routes,
    summing to `routes` exactly (the remainder goes to the first)."""
    w = [1.0 / (i + 1) for i in range(vpcs)]
    sizes = [int(routes * x / sum(w)) for x in w]
    for i in range(routes - sum(sizes)):
        sizes[i % vpcs] += 1
    return sizes


def vpc_routes(n: int, vpc: int) -> list:
    """n distinct routes of one VPC, as its RouteTable holds them:
    `gen.distinct_routes`' shapes (/8../24 inside 10/8..22/8, the
    length mix `route_length_counts` gives at this size), walked from
    a per-VPC offset, so two VPCs hold different prefixes of the same
    space and some the same."""
    added = []
    for m, count in gen.route_length_counts(n).items():
        space = gen.ROUTE_OCTETS * 2 ** (m - 8)
        for k in range(count):
            p = (k * gen._STRIDE + m + vpc * 131) % space
            added.append((((10 + p % gen.ROUTE_OCTETS) << 24)
                          | ((p // gen.ROUTE_OCTETS) << (32 - m)), m))
    return gen.route_table_order(added)


class SwitchVpc(Deployment):
    kinds = ("route", "acl")
    # route: answered from the next VPC's table (another tenant's);
    # ACL: the port range ignored
    controls = {"route": "next_vpc", "acl": "noport"}

    def __init__(self, config: dict, seed: int):
        super().__init__()
        sizes = config["sizes"]
        self._work: dict = {}
        self.views: list = []
        self.plain = {
            "route": [vpc_routes(n, v) for v, n in enumerate(
                vpc_sizes(sizes["routes"], sizes["vpcs"]))],
            "acl": gen.north_star_acls(sizes["acls"]),
        }

    def install(self) -> None:
        from vproxy_tpu.utils.ip import Network, mask_bytes
        t0 = time.monotonic()
        ts = CidrTableSet("v4")
        self.views = [ts.view() for _ in self.plain["route"]]
        for view, nets in zip(self.views, self.plain["route"]):
            view.set_networks([Network(int(v).to_bytes(4, "big"),
                                       mask_bytes(m)) for v, m in nets])
        self.install_s["route"] = time.monotonic() - t0
        held = [v.size() for v in self.views]
        if held != [len(t) for t in self.plain["route"]]:
            raise RuntimeError(f"the set's tables hold {held}")
        self.matchers = {
            "route": ts,
            "acl": self.install_cidr("acl", self.plain["acl"], True)}

    def pool_kind(self, kind: str, n: int, traffic: dict, seed: int) -> list:
        if kind == "acl":
            return gen.cidr_pool(n, self.plain["acl"], seed,
                                 traffic["miss_every"], True)
        # a destination inside a route drawn uniformly from all of them
        # (a VPC's share of lookups follows its share of routes), asked
        # of that route's VPC; a missing index asks from 100/8..112/8
        flat = [(v, net) for v, t in enumerate(self.plain["route"])
                for net in t]
        rs = gen.rng_for(seed, "vpcpool")
        out, seen = [], set()
        while len(out) < n:
            vpc, net = flat[int(rs.integers(0, len(flat)))]
            a = gen._addr_in(net, rs)
            if gen._is_miss(len(out), traffic["miss_every"]):
                a = bytes([a[0] + 90]) + a[1:]
            if a not in seen:
                seen.add(a)
                out.append((a, None, vpc))
        return out

    def answers_kind(self, kind: str, queries: list, broken: bool,
                     seed: int):
        if kind == "acl":
            return ref.cidr_first_match(self.plain["acl"], queries,
                                        with_port=not broken)
        tables = self.plain["route"]
        return reference_vpc.vpc_first_match(
            tables, [((q[2] + broken) % len(tables), q[0]) for q in queries])

    def work(self, kind: str, q: tuple) -> int:
        """Bytes one lookup needs: `work.cidr_bytes` of the table it
        names — the named VPC's, plus the table-id row."""
        key = kind if kind == "acl" else q[2]
        if key not in self._work:
            self._work[key] = work.cidr_bytes(self.plain["acl"], True) \
                if kind == "acl" else TABLE_ID + work.cidr_bytes(
                    self.plain["route"][q[2]], False)
        return self._work[key]

    def submit_call(self, kind: str, svc, q: tuple):
        if kind == "acl":
            return super().submit_call(kind, svc, q)
        view = self.views[q[2]] if self.views else None  # the control
        return partial(svc.submit_cidr, view, q[0], None)

    def warm(self, kind: str, queries: list, buckets: list) -> int:
        if kind == "acl":
            return super().warm(kind, queries, buckets)
        ts = self.matchers["route"]
        snap = ts.snapshot()
        for b in buckets:
            part = (queries * (b // len(queries) + 1))[:b]
            np.asarray(ts.dispatch_snap(
                snap, [q[0] for q in part], None,
                [self.views[q[2]].key for q in part], pad_to=b, sync=False))
        return len(buckets)


def build(config: dict, seed: int) -> SwitchVpc:
    return SwitchVpc(config, seed)
