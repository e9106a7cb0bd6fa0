"""The plain reference of a multi-VPC switch: RouteTable.lookup a VNI.

Upstream's switch holds one `Table` a VNI, each with its own
`RouteTable` (vswitch/Table.java:13, RouteTable.java:15), and a routed
frame is looked up in the table of the VPC it arrived in and in no
other: tenants reuse the same address space, so the same prefix in two
VPCs names two different routes. Here a lookup is (vpc, address) and a
deployment's routes are one RouteTable-ordered list a VPC
(`gen.route_table_order`); the answer is the index, in the named VPC's
own list, of the first route that contains the address — `reference.
cidr_first_match`, run once a VPC over that VPC's lookups. A VPC the
deployment does not hold answers -1. Plain data only: this file
imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

import reference as ref


def vpc_first_match(tables: list, lookups: list) -> np.ndarray:
    """tables[v]: VPC v's routes (value_u32, masklen) in RouteTable
    order; lookups: [(vpc, addr4)] -> int32 [n], the first containing
    route of the named VPC by that VPC's own indices, -1 for none."""
    out = np.full(len(lookups), -1, np.int32)
    by_vpc: dict = {}
    for i, (vpc, _addr) in enumerate(lookups):
        by_vpc.setdefault(vpc, []).append(i)
    for vpc, at in by_vpc.items():
        if 0 <= vpc < len(tables) and tables[vpc]:
            out[at] = ref.cidr_first_match(
                tables[vpc], [(lookups[i][1],) for i in at], False)
    return out
