"""The one place the benchmark touches the program.

What is taken from `vproxy_tpu`: the system under test (ClassifyService,
the matchers and their TableInstaller; for a burst driver the switch's
`VpcNetwork`s and `route_lookup_burst`), its counters, and its
`engine/queue_wait` spans. Everything that decides a number — traffic,
reference, reduction, peaks — lives beside this file and imports none
of it. Import this module only after `apply_operator_settings`: the
program reads its environment when it is imported.
"""
from __future__ import annotations

import os
import time

import numpy as np

import gen


def apply_operator_settings(config: dict) -> None:
    """The deployment's operator settings, as its configuration file
    lists them, and no other variable of the program."""
    for k, v in config.get("operator_settings", {}).items():
        os.environ[k] = str(v)


def compile_cache() -> str:
    import jax
    from vproxy_tpu.utils.jaxenv import compile_cache_dir
    path = compile_cache_dir()
    # every program of a cell is found again by its next run, also the
    # small buckets that compile in under a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileLog:
    """Backend compile requests as jax.monitoring reports them (a
    persistent-cache hit is a request with a small duration)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon
        self.compiles: list = []   # (monotonic time, name, seconds)
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event == self.EVENT:
            self.compiles.append((time.monotonic(),
                                  str(kw.get("fun_name", "?")), secs))

    def between(self, t0: float, t1: float) -> list:
        return [(n, s) for t, n, s in self.compiles if t0 <= t <= t1]


def hint_of(q: tuple):
    from vproxy_tpu.rules.ir import Hint
    return Hint(host=q[0], port=q[1], uri=q[2])


class Deployment:
    """Installed tables of one configuration. A builder fills `matchers`
    (kind -> matcher) and `plain` (the rule data the reference reads),
    and says for each query kind it serves how to make n queries
    (`pool_kind`), what the reference answers (`answers_kind`), which
    guarantee its control breaks (`controls`) and what work a lookup
    needs (`work`). Pools and answers of a traffic mix are put together
    here, kind by kind, so a new mix of served kinds is a data file."""

    kinds: tuple = ()
    controls: dict = {}     # kind -> name of the guarantee its control breaks
    PICK_KINDS = ("cpick",)  # answered as (verdict, pick)

    def __init__(self):
        self.matchers: dict = {}
        self.plain: dict = {}      # plain rule data, by table name
        self.install_s: dict = {}

    def has_pick(self, kind: str) -> bool:
        return kind in self.PICK_KINDS

    def pool(self, traffic: dict, seed: int) -> list:
        """-> [(kind, query)] by pool rank, kinds interleaved as the
        traffic file lists them."""
        missing = [k for k in traffic["kinds"] if k not in self.kinds]
        if missing:
            raise ValueError(f"{type(self).__name__} serves {self.kinds}, "
                             f"not {missing}")
        return gen.interleave(
            traffic["kinds"], traffic["pool"],
            lambda kind, n: self.pool_kind(kind, n, traffic, seed))

    def answers(self, pool: list, control: bool = False, seed: int = 0):
        """Reference answers by pool rank -> int32 [n, 2]: (verdict,
        pick), pick = gen.NOPICK where the kind has none. control: each kind
        answered with its own guarantee broken."""
        out = np.full((len(pool), 2), gen.NOPICK, np.int32)
        for kind in dict.fromkeys(k for k, _q in pool):
            at = [i for i, (k, _q) in enumerate(pool) if k == kind]
            res = np.asarray(self.answers_kind(
                kind, [pool[i][1] for i in at], control, seed))
            if res.ndim == 1:
                out[at, 0] = res
            else:
                out[at] = res
        return out

    def pool_kind(self, kind: str, n: int, traffic: dict, seed: int) -> list:
        raise NotImplementedError

    def answers_kind(self, kind: str, queries: list, broken: bool,
                     seed: int):
        raise NotImplementedError

    # ---- install through the TableInstaller (set_rules / set_networks)
    def install_hint(self, rules: list, payload=None):
        from vproxy_tpu.rules.engine import HintMatcher
        from vproxy_tpu.rules.ir import HintRule
        hm = HintMatcher()
        t0 = time.monotonic()
        hm.set_rules([HintRule(host=h, port=p, uri=u) for h, p, u in rules],
                     payload=payload)
        self.install_s["hint"] = time.monotonic() - t0
        if hm.size() != len(rules):
            raise RuntimeError(f"hint table holds {hm.size()} rules, "
                               f"want {len(rules)}")
        return hm

    def install_cidr(self, name: str, nets: list, acl: bool):
        from vproxy_tpu.rules.engine import CidrMatcher
        from vproxy_tpu.rules.ir import AclRule, Proto
        from vproxy_tpu.utils.ip import Network, mask_bytes
        networks = [Network(int(n[0]).to_bytes(4, "big"), mask_bytes(n[1]))
                    for n in nets]
        acls = [AclRule(f"r{i}", networks[i], Proto.TCP, n[2], n[3],
                        i % 2 == 0) for i, n in enumerate(nets)] \
            if acl else None
        cm = CidrMatcher()
        t0 = time.monotonic()
        cm.set_networks(networks, acl=acls)
        self.install_s[name] = time.monotonic() - t0
        if cm.size() != len(nets):
            raise RuntimeError(f"{name} table holds {cm.size()}, "
                               f"want {len(nets)}")
        return cm

    def install_pair(self, hm, names: list, m: int):
        from vproxy_tpu.rules.maglev import FusedPair, MaglevMatcher
        t0 = time.monotonic()
        mm = MaglevMatcher([(s, 1) for s in names], m=m)
        self.install_s["maglev"] = time.monotonic() - t0
        return FusedPair(hm, mm)

    def backends(self) -> dict:
        return {k: getattr(m, "backend", "?")
                for k, m in self.matchers.items()}

    def table_bytes(self) -> int:
        total = 0
        for m in {id(m): m for m in self.matchers.values()}.values():
            for part in (m, getattr(m, "hm", None), getattr(m, "mm", None)):
                fn = getattr(part, "published_table_bytes", None)
                if fn is not None:
                    total += fn()
        return total

    # ---- the calls the window drives
    def submit_call(self, kind: str, svc, q: tuple):
        """-> f(cb): one submit of query q through the service."""
        from functools import partial
        m = self.matchers.get(kind)     # None under the control
        if kind == "hint":
            return partial(svc.submit_hint, m, hint_of(q))
        if kind == "route":
            return partial(svc.submit_cidr, m, q[0], None)
        if kind == "acl":
            return partial(svc.submit_cidr, m, q[0], q[1])
        if kind == "cpick":
            return partial(svc.submit_classify_pick, m, hint_of(q), q[3],
                           q[4])
        raise ValueError(f"unknown query kind {kind!r}")

    def warm(self, kind: str, queries: list, buckets: list) -> int:
        """Compile (or load from the cache) every program the window can
        form for this kind: one direct dispatch_snap per pad bucket and
        per probe need of the pool's hosts; bucket 32 also from 28
        queries, where the program's small-batch encoder ends."""
        m = self.matchers[kind]
        snap = m.snapshot()
        if kind in ("route", "acl"):
            variants = [queries]
        else:
            need = [q[0].count(".") for q in queries]
            variants = []
            for v in sorted(set(need)):
                top = [q for q, n in zip(queries, need) if n == v]
                rest = [q for q, n in zip(queries, need) if n < v]
                variants.append(top[:1] + rest + top[1:])
        calls = 0
        for b in buckets:
            for qs in variants:
                for n in sorted({b, min(b, 28)} if b == 32 else {b}):
                    part = (qs * (n // len(qs) + 1))[:n]
                    if kind == "hint":
                        out = m.dispatch_snap(snap, [hint_of(q) for q in part],
                                              pad_to=b, sync=False)
                    elif kind == "cpick":
                        out = m.dispatch_snap(
                            snap, [(hint_of(q), q[3], q[4]) for q in part],
                            pad_to=b, sync=False)
                    else:
                        out = m.dispatch_snap(
                            snap, [q[0] for q in part],
                            [q[1] for q in part] if kind == "acl" else None,
                            pad_to=b, sync=False)
                    np.asarray(out)
                    calls += 1
        return calls


def new_service():
    """The classify service as every plane gets it: mode from the
    environment (the configuration's VPROXY_TPU_CLASSIFY)."""
    from vproxy_tpu.rules.service import ClassifyService
    return ClassifyService()


CROSSED = -2    # a verdict no reference gives: a rule of another table


class SwitchRoutes:
    """A switch's routing state without its sockets: one `VpcNetwork` a
    VNI whose routes are one table each of the switch's (v4, v6)
    `CidrTableSet`, made as `Switch.add_network` / `Switch.route_sets`
    make them, and `route_lookup_burst`, the program's own function
    that `vswitch/stack.py _route_flush` hands a drained burst to: ONE
    synchronous `CidrTableSet.match` on the caller's thread, no
    ClassifyService. `tables[v]`: VPC v's routes (value_u32, masklen)
    in RouteTable order, installed the largest VPC first.

    The tables go in as `RouteTable(rules_v4=...)` + `sync_routes()`,
    not `set_routes()`: `RouteTable.add` scans the table twice a route
    (10 s for 3,000 routes, minutes for the 10,540 of the largest VPC
    — PERF.md §7); the order is the same list (tests/test_burst_cell.py).

    The set's host paths (`index_snap`: the small-set scan, the `host`
    backend, the failover; `oracle_snap`) are counted where they are
    entered: a burst the device served never reaches them."""

    def __init__(self, tables: list):
        from vproxy_tpu.rules.engine import CidrTableSet
        from vproxy_tpu.rules.ir import RouteRule, RouteTable
        from vproxy_tpu.utils.ip import Network, mask_bytes
        from vproxy_tpu.vswitch import network as N
        self.route_lookup_burst = N.route_lookup_burst
        self.sets = (CidrTableSet("v4"), CidrTableSet("v6"))
        self.route_set = self.sets[0]   # the cell's routes are v4
        self.host_lookups = 0
        for attr in ("index_snap", "oracle_snap"):
            setattr(self.route_set, attr,
                    self._counted(getattr(self.route_set, attr)))
        space = Network(bytes([10, 0, 0, 0]), mask_bytes(8))
        self.networks = [N.VpcNetwork(v + 1, space, route_sets=self.sets)
                         for v in range(len(tables))]
        for v, nets in enumerate(tables):
            net = self.networks[v]
            net.routes = RouteTable(rules_v4=[
                RouteRule(f"v{v}r{i}", Network(int(a).to_bytes(4, "big"),
                                               mask_bytes(m)), to_vni=v + 1)
                for i, (a, m) in enumerate(nets)])
            net.sync_routes()

    def _counted(self, fn):
        def counted(*a, **kw):
            self.host_lookups += 1
            return fn(*a, **kw)
        return counted

    def views(self) -> list:
        """Each VPC's v4 table of the set, as `SwitchVpc.views`."""
        return [n._matcher_v4 for n in self.networks]

    def lookups(self, pool: list) -> list:
        """Pool rank -> the (VpcNetwork, dst) a burst carries for it."""
        return [(self.networks[q[2]], q[0]) for _k, q in pool]

    def verdicts(self, results: list, vpcs: np.ndarray) -> np.ndarray:
        """The RouteRules (or None) the bursts returned, flat -> int32
        [n]: each rule's index in the table of the VPC the lookup named,
        -1 for None, CROSSED for a rule of another VPC's table or an
        object that is no rule of this switch. After the window only."""
        where = {id(None): (-1, -1)}
        for v, net in enumerate(self.networks):
            for i, r in enumerate(net.routes.rules_v4):
                where[id(r)] = (v, i)
        ids = np.fromiter(map(id, results), np.int64, len(results))
        uniq, inv = np.unique(ids, return_inverse=True)
        tab = np.array([where.get(u, (-2, CROSSED))
                        for u in uniq.tolist()], np.int32).reshape(-1, 2)
        vpc, idx = tab[inv, 0], tab[inv, 1].copy()
        idx[(vpc >= 0) & (vpc != vpcs)] = CROSSED
        return idx

    def bench_spans(self) -> list:
        """What a traced run wraps (Instrument): the whole burst call,
        the set's match (dispatch + the blocking read) and its
        dispatch_snap (encode + launch), innermost booked first."""
        return [(self, "route_lookup_burst", "bench/burst"),
                (self.route_set, "match", "bench/match"),
                (self.route_set, "dispatch_snap", "bench/submit")]

    def counters(self) -> dict:
        from vproxy_tpu.rules import engine as E
        return {"launches": E.dispatch_launches_total(),
                "host_arrays": E.launch_host_arrays_total(),
                "host_lookups": self.host_lookups,
                "backend": self.route_set.backend,
                "generation": self.route_set.generation}

    def close(self) -> None:
        self.networks = []


def pad_buckets(outstanding: int) -> list:
    from vproxy_tpu.rules.engine import pad_batch
    from vproxy_tpu.rules.service import PAD_LO
    return sorted({pad_batch(n, lo=PAD_LO) for n in range(1, outstanding + 1)})


def counters(svc) -> dict:
    from vproxy_tpu.rules import engine as E
    st = svc.stats
    d = {k: getattr(st, k) for k in (
        "queries", "dispatches", "device_queries", "oracle_queries",
        "failovers", "max_batch", "inline_fast", "budget_reroutes")}
    d["last_failover"] = st.last_failover
    d["launches"] = E.dispatch_launches_total()
    d["fused_dispatches"] = E.fused_dispatches_total()
    return d


class ErrorLog:
    """The program's error and alert log lines (they also go to stderr),
    kept so that a `-1` from a dispatcher exception can be read from the
    run's own output."""

    def __init__(self):
        import traceback

        from vproxy_tpu.utils import log
        self.lines: list = []
        self._log, self._orig = log, log._emit

        def emit(level, channel, msg, exc=False):
            if level in ("error", "alert") and len(self.lines) < 20:
                tail = traceback.format_exc().strip().splitlines()[-1] \
                    if exc else ""
                self.lines.append(f"{level} [{channel}] {msg} {tail}".strip())
            return self._orig(level, channel, msg, exc)
        log._emit = emit

    def close(self) -> None:
        self._log._emit = self._orig


# ------------------------------------------------------- traced-run spans

class Instrument:
    """Spans around the calls into each layer, recorded from here and
    only in the traced run: a `jax.profiler.TraceAnnotation` each (so
    they sit on the profiler's clock beside the device's operations)
    and a total of their own for the per-layer readers."""

    def __init__(self, svc, sample_every: int):
        """svc: whatever the driver drives. Of a ClassifyService the
        three calls below are wrapped where they exist; a service of
        another kind names its own in `bench_spans()` -> [(owner,
        attribute, span)]."""
        import jax
        from vproxy_tpu.ops import hashmatch as H
        from vproxy_tpu.ops import tables as T
        from vproxy_tpu.rules import maglev as MG
        from vproxy_tpu.utils import trace
        self.totals: dict = {}     # span -> [calls, ns, items]
        self.queue_wait_us: list = []
        self._trace = trace
        self._undo: list = []
        self._ann = jax.profiler.TraceAnnotation
        self._wrap(H, "encode_hint_queries", "bench/encode", items=True)
        self._wrap(T, "encode_ips", "bench/encode", items=True)
        self._wrap(MG, "flow_slots", "bench/encode")
        for attr, span in (("_device_submit", "bench/submit"),
                           ("_finish_inflight", "bench/readback_deliver"),
                           ("_deliver", "bench/deliver")):
            if hasattr(svc, attr):
                self._wrap(svc, attr, span)
        for owner, attr, span in getattr(svc, "bench_spans", list)():
            self._wrap(owner, attr, span)
        self._prev_sample = trace.sample_every()
        trace.reset()
        trace.configure(sample_every)

    def _wrap(self, owner, attr: str, span: str,
              items: bool = False) -> None:
        """items: the first argument is the batch of queries encoded."""
        orig = getattr(owner, attr)
        shadow = attr not in vars(owner)   # a method, shadowed on svc
        tot = self.totals.setdefault(span, [0, 0, 0])
        ann = self._ann

        def wrapped(*a, **kw):
            t0 = time.perf_counter_ns()
            with ann(span):
                out = orig(*a, **kw)
            tot[0] += 1
            tot[1] += time.perf_counter_ns() - t0
            if items:
                tot[2] += len(a[0])
            return out
        setattr(owner, attr, wrapped)
        self._undo.append((owner, attr, orig, shadow))

    def sample(self) -> int:
        """Trace context for one submit: nonzero for every Nth call (the
        program's own 1-in-N decider)."""
        return self._trace.maybe_sample()

    def bind(self, tid: int):
        return self._trace.bind(tid)

    def drain(self) -> None:
        """Move the program's buffered `engine/queue_wait` spans out of
        its bounded trace buffer (512 traces) into this run's list."""
        tr = self._trace
        for tid in tr.trace_ids():
            for s in tr.get_trace(tid):
                if s["plane"] == "engine" and s["span"] == "queue_wait":
                    self.queue_wait_us.append(s["dur_ns"] / 1000.0)
        tr.reset()

    def close(self) -> None:
        self.drain()
        self._trace.configure(self._prev_sample)
        for owner, attr, orig, shadow in reversed(self._undo):
            if shadow:
                delattr(owner, attr)
            else:
                setattr(owner, attr, orig)
        self._undo.clear()
