"""h2lb-groups256: `lb_groups`' deployment as the program serves it to
HTTP/2 clients — one `Upstream` whose 256 `source` groups (weight 0)
carry the Host rules that name them, several to a group, plus one
`default` group (weight 10, no rule) that the round robin always lands
on; a `TcpLB(protocol="h2")` in front (the driver's). Each stream's
`:authority` is classified and its group's pick made in one
`Upstream.next_async` submit.

The backends are real h2c endpoints: one listening socket each on
127.0.0.1, reserved here (so every identity is known before install,
also to the control) and handed to the backend process by the driver.
A member's identity is `g<i>|127.0.0.1:<port>`, as
ServerGroup.maglev_identity spells it. The members `lb_groups` draws
down are taken down at install by their group's own health edge.

Query form
    h2     (authority, 0, "/", client_ip4, None)   the hint the h2
           processor asks (Hint.of_host_uri(authority, ":path")) and the
           connection's source address; port None: method `source`
"""
from __future__ import annotations

import socket
import time

import gen
import reference_h2
from builders.lb_groups import WEIGHT, LbGroups
# a program whose Upstream takes one HintRule a group raises at
# `Upstream.add_many` in install, seconds in, before any process of the
# cell is started
from vproxy_tpu.components.upstream import Upstream

DEFAULT_MEMBERS = 4
GROUP_M = 4099      # maglev.GROUP_M: the program's table size a group
ROWS_WAIT_S = 120.0


def source_ip(s: int) -> bytes:
    """Client connection s binds 127.1.0.(s + 1)."""
    return bytes([127, 1, 0, s + 1])


class H2Lb(LbGroups):
    kinds = ("h2",)
    PICK_KINDS = ("h2",)
    controls = {"h2": "other_group"}

    def __init__(self, config: dict, seed: int):
        super().__init__(config, seed)
        groups = self.sizes["groups"]
        self.pub_rules, self.pub_group = reference_h2.published(
            self.plain["hint"], self.rule_group, groups)
        self.listeners: list = []       # [socket], member order, then default
        self.idents: list = []          # identity of each listener
        self.answer_of: list = []       # (group, index in healthy) or None
        self.members = [[(self._reserve(f"g{g}"), up) for _name, up in ms]
                        for g, ms in enumerate(self.members)]
        self.healthy = [[name for name, up in ms if up]
                        for ms in self.members]
        self.default = [self._reserve("default")
                        for _ in range(DEFAULT_MEMBERS)]
        for name in self.idents:
            g = int(name[1:name.index("|")]) if name[0] == "g" else groups
            hl = self.healthy[g] if g < groups else self.default
            self.answer_of.append((g, hl.index(name)) if name in hl
                                  else None)
        self.pool_group: list = []      # the group each pool entry aims at
        self.upstream = None
        self._hc = None

    def _reserve(self, alias: str) -> str:
        """A listening socket on 127.0.0.1 -> the member's identity."""
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        s.listen(1024)
        name = f"{alias}|127.0.0.1:{s.getsockname()[1]}"
        self.listeners.append(s)
        self.idents.append(name)
        return name

    def install(self) -> None:
        from vproxy_tpu.components.elgroup import EventLoopGroup
        from vproxy_tpu.components.servergroup import (HealthCheckConfig,
                                                       ServerGroup)
        from vproxy_tpu.rules.ir import HintRule
        t0 = time.monotonic()
        elg = EventLoopGroup("h2lb-hc", 1)
        hc = HealthCheckConfig(protocol="none")
        made, down = [], []
        for alias, ms in [(f"g{g}", ms) for g, ms in enumerate(self.members)] \
                + [("default", [(n, True) for n in self.default])]:
            sg = ServerGroup(alias, elg, hc, "source")
            for b, (name, up) in enumerate(ms):
                sg.add(f"m{b}", "127.0.0.1", int(name.rsplit(":", 1)[1]),
                       WEIGHT)
                if not up:
                    down.append((sg, f"m{b}"))
            made.append(sg)
        self._hc = (elg, made)
        lp = elg.next()
        lp.call_sync(lambda: None, timeout=600)     # every check's UP verdict

        def take_down() -> None:
            for sg, name in down:       # the group's own DOWN edge
                chk = sg._checkers[name]
                for _ in range(hc.down):
                    chk._result(False)
        lp.call_sync(take_down, timeout=600)
        self.install_s["groups"] = time.monotonic() - t0
        rules: list = [[] for _ in self.members]
        for (h, p, u), g in zip(self.pub_rules, self.pub_group):
            rules[g].append(HintRule(host=h, port=p, uri=u))
        ups = Upstream("h2lb")
        t0 = time.monotonic()
        try:
            ups.add_many([(sg, 0, rs) for sg, rs in zip(made, rules)]
                         + [(made[-1], WEIGHT, [])])
        except Exception:
            # the groups' loop ends before the interpreter does: a loop
            # thread left to its teardown can abort the process
            self.close()
            raise
        held = sum(1 for names in self.healthy if names) + 1
        end = time.monotonic() + ROWS_WAIT_S
        while ups._picks.size() < held and time.monotonic() < end:
            time.sleep(0.05)
        self.install_s["hint"] = time.monotonic() - t0
        self.upstream = ups
        self.matchers = {"cpick": ups._pair}
        bad_down = sum(s.healthy for sg, name in down for s in sg.servers
                       if s.name == name)
        if ups._matcher.size() != len(self.pub_rules) \
                or ups._picks.size() != held or bad_down:
            raise RuntimeError(
                f"the upstream holds {ups._matcher.size()} rules and "
                f"{ups._picks.size()} pick tables, {bad_down} members up "
                f"that should be down (want {len(self.pub_rules)}, {held}, "
                f"0)")

    def close(self) -> None:
        """The upstream, the groups and their checks' loop, and this
        process's copies of the listening sockets."""
        if self.upstream is not None:
            self.upstream.close()
            self.upstream = None
        if self._hc is not None:
            elg, made = self._hc
            for sg in made:
                sg.close()
            elg.close()
            self._hc = None
        for s in self.listeners:
            s.close()

    def pool_kind(self, kind: str, n: int, traffic: dict, seed: int) -> list:
        """n distinct (authority, source) entries; entry j comes from
        client connection j % connections. 1 in `miss_every` aims at a
        host no rule holds; 3 in 4 ask for `www.` under the rule's
        domain."""
        rules, tag = self.plain["hint"], self.tag
        conns = traffic["connections"]
        groups = self.sizes["groups"]
        rs = gen.rng_for(seed, "h2pool")
        out, seen, self.pool_group = [], set(), []
        while len(out) < n:
            j = len(out)
            aim = int(rs.integers(0, len(rules)))
            miss = gen._is_miss(j, traffic["miss_every"])
            host = gen.miss_host(aim, tag) if miss else rules[aim][0]
            if j % 4:
                host = "www." + host
            if (host, j % conns) in seen:
                continue
            seen.add((host, j % conns))
            out.append((host, 0, "/", source_ip(j % conns), None))
            self.pool_group.append(groups if miss else self.rule_group[aim])
        return out

    def answers_kind(self, kind: str, queries: list, broken: bool,
                     seed: int):
        """-> int32 [n, 2]: (group, member index in its healthy list);
        group `groups` is the default group."""
        return reference_h2.answers(
            self.pub_rules, self.pub_group, self.healthy, self.default,
            GROUP_M, queries, shift=int(broken))

    def warm(self, kind: str, queries: list, buckets: list) -> int:
        """The pair's programs, as the h2 plane's hints ask them."""
        return super().warm("cpick", queries, buckets)


def build(config: dict, seed: int) -> H2Lb:
    return H2Lb(config, seed)
