"""switch-vpc64 as the switch itself holds it: the same 64 RouteTables,
from the same `vpc_routes()`, the same 5,000-entry ACL table beside
them — but each VPC is a `VpcNetwork` whose routes are one table of the
switch's `CidrTableSet` (`program.SwitchRoutes`), so a burst can be
routed through `vswitch.network.route_lookup_burst`, the call
`vswitch/stack.py _route_flush` makes, and not only through
`ClassifyService.submit_cidr`. A traffic file names this builder
(`"builder": "switch_vpc_networks"`); `switch_vpc.py` and what
`route-w1024` installs, warms and submits are untouched. Pools, answers,
work and controls are `SwitchVpc`'s.
"""
from __future__ import annotations

import time

import program
from builders.switch_vpc import SwitchVpc


class SwitchVpcNetworks(SwitchVpc):
    switch = None   # program.SwitchRoutes, once installed

    def install(self) -> None:
        t0 = time.monotonic()
        self.switch = program.SwitchRoutes(self.plain["route"])
        self.install_s["route"] = time.monotonic() - t0
        self.views = self.switch.views()
        held = [v.size() for v in self.views]
        if held != [len(t) for t in self.plain["route"]]:
            raise RuntimeError(f"the set's tables hold {held}")
        self.matchers = {
            "route": self.switch.route_set,
            "acl": self.install_cidr("acl", self.plain["acl"], True)}


def build(config: dict, seed: int) -> SwitchVpcNetworks:
    return SwitchVpcNetworks(config, seed)
