"""Upstream — groups-of-groups with hint-based selection on the classify
engine.

Reference: component/svrgroup/Upstream.java — weighted-RR across
ServerGroups (seq :68-116), hint selection via searchForGroup (:187-198).
THE difference: the linear annotation scan is replaced by the device
HintMatcher (vproxy_tpu/rules/engine.py) — the rule table lives in HBM
and single queries or micro-batches go through the same compiled kernel.
Beside it the upstream owns a maglev.MaglevTableSet — one pick table a
`source` group, a row each — and the GroupedPair over both: the async
accept path (next_async / seek_async without a `fam`) asks for the
group AND the group's pick in one submit.
"""
from __future__ import annotations

import threading
import time
from typing import Optional, Sequence

from ..rules.engine import HintMatcher
from ..rules.ir import Hint, HintRule
from ..rules.maglev import GroupedPair, MaglevTableSet
from ..utils.metrics import accept_stage_observe
from .servergroup import Connector, ServerGroup


class GroupHandle:
    def __init__(self, group: ServerGroup, weight: int,
                 annotations: Optional[HintRule] = None):
        self.alias = group.alias
        self.group = group
        self.weight = weight
        self.annotations = annotations or HintRule()
        # set by Upstream.add: the group's row of the upstream's
        # pick-table set, and the change listener that re-installs it
        self.ref = -1
        self.on_edge = None

    def pick_row(self):
        """What the group's row of the pick-table set holds (called on
        the installer thread): the table `_source_next` reads at this
        health generation, with that generation and the member list the
        table indexes as the row's payload; None for a group that is
        not `source` or has no healthy member."""
        g = self.group
        if g.method != "source":
            return None
        hv, servers, table = g.maglev_row()
        if not servers:
            return None
        return table, [g.maglev_identity(s) for s in servers], (hv, servers)

    def merged_rule(self) -> HintRule:
        """Handle annotations take precedence over the group's own
        (Hint.matchLevel merges in that order, Hint.java:104-117)."""
        g = self.group.annotations
        return HintRule(
            host=self.annotations.host if self.annotations.host is not None else g.host,
            port=self.annotations.port if self.annotations.port != 0 else g.port,
            uri=self.annotations.uri if self.annotations.uri is not None else g.uri,
        )


class Upstream:
    def __init__(self, alias: str, backend: Optional[str] = None):
        self.alias = alias
        self.handles: list[GroupHandle] = []
        self._matcher = HintMatcher([], backend=backend)
        # analytics attribution: the ClassifyService credits device
        # launches/batch occupancy to this upstream by this name
        self._matcher.owner_alias = alias
        self._picks = MaglevTableSet(backend=self._matcher.backend)
        self._pair = GroupedPair(self._matcher, self._picks)
        self._pair.owner_alias = alias
        self._wrr_seq: list[int] = []
        self._wrr_groups: list[GroupHandle] = []
        self._wrr_cursor = 0
        self._lock = threading.Lock()
        # mutation listeners, fired AFTER a recalc publishes (lock
        # released): the accept lanes register their generation bump +
        # lane-entry recompile here so add/remove/annotation edits
        # invalidate the C-resident route table immediately
        self._listeners: list = []

    def add_listener(self, cb) -> None:
        self._listeners.append(cb)

    def remove_listener(self, cb) -> None:
        try:
            self._listeners.remove(cb)
        except ValueError:
            pass

    def _fire(self) -> None:
        for cb in list(self._listeners):
            try:
                cb()
            except Exception:
                pass

    # ------------------------------------------------------------- admin

    def add(self, group: ServerGroup, weight: int = 10,
            annotations: Optional[HintRule] = None) -> GroupHandle:
        with self._lock:
            if any(h.group is group for h in self.handles):
                raise ValueError(f"group {group.alias} already in upstream {self.alias}")
            h = GroupHandle(group, weight, annotations)
            h.ref = self._picks.alloc()
            h.on_edge = lambda: self._row_edge(h)
            group.on_change(h.on_edge)
            h.on_edge()     # ahead of the rules that name the row
            self.handles.append(h)
            self._recalc()
        self._fire()
        return h

    def remove(self, group: ServerGroup) -> None:
        with self._lock:
            for i, h in enumerate(self.handles):
                if h.group is group:
                    del self.handles[i]
                    self._recalc()
                    group.off_change(h.on_edge)
                    self._picks.release(h.ref)
                    break
            else:
                raise KeyError(group.alias)
        self._fire()

    def _row_edge(self, h: GroupHandle) -> None:
        """A health or membership edge of ONE group: enqueue its own
        row, built on the installer thread — bump and defer, the
        listener may run under the group's lock. A group that is not
        `source` and holds no table has nothing to install: a `wrr`
        group's edges never reach the installer."""
        if h.group.method == "source" \
                or h.ref in self._picks.snapshot().rows:
            self._picks.install(h.ref, h.pick_row, wait=False)

    def close(self) -> None:
        """The upstream is gone: its groups live on and stop feeding
        its pick-table set."""
        with self._lock:
            for h in self.handles:
                h.group.off_change(h.on_edge)
                self._picks.release(h.ref)

    def set_annotations(self, group: ServerGroup, annotations: HintRule) -> None:
        with self._lock:
            for h in self.handles:
                if h.group is group:
                    h.annotations = annotations
                    self._recalc()
                    break
            else:
                raise KeyError(group.alias)
        self._fire()

    def _recalc(self) -> None:
        # the handle list is the rules' payload: published atomically
        # with the compiled table so async classify results map their
        # index through the SAME generation (see HintMatcher._pub)
        self._pair.set_rules([h.merged_rule() for h in self.handles],
                             payload=list(self.handles),
                             groups=[h.ref for h in self.handles])
        groups = [h for h in self.handles if h.weight > 0]
        self._wrr_groups = groups
        self._wrr_seq = ServerGroup._wrr_compute(groups) if groups else []
        self._wrr_cursor = 0

    # ------------------------------------------------------------- data

    def search_for_group(self, hint: Hint) -> Optional[GroupHandle]:
        """Sync hint search against ONE matcher generation: the index
        is interpreted through the SNAPSHOT's payload (the handle list
        registered with those rules), never `self.handles` — a standby
        install publishes seconds after add/remove mutated the live
        list, and a published-generation index into the mutated list
        would route wrong (or past the end). Served from the exact
        O(probes) host index, same winner as the oracle/device."""
        m = self._matcher
        snap = m.snapshot()
        idx = m.index_snap(snap, hint)
        handles = m.snap_payload(snap)
        if handles is None:  # pre-first-publish: the live list
            handles = self.handles
        return handles[idx] if 0 <= idx < len(handles) else None

    def search_batch(self, hints: Sequence[Hint]) -> list[Optional[GroupHandle]]:
        m = self._matcher
        snap = m.snapshot()  # one generation for every answer
        handles = m.snap_payload(snap)
        if handles is None:
            handles = self.handles
        out = []
        for h in hints:
            i = m.index_snap(snap, h)
            out.append(handles[i] if 0 <= i < len(handles) else None)
        return out

    def seek(self, source_ip: bytes, hint: Hint,
             fam: Optional[str] = None,
             exclude: Optional[set] = None) -> Optional[Connector]:
        h = self.search_for_group(hint)
        if h is not None:
            return h.group.next(source_ip, fam, exclude)
        return None

    # --------------------------------------- host-only (retry) selection

    def _search_host(self, hint: Hint) -> Optional[GroupHandle]:
        """search_for_group on the HOST index only (exact oracle parity,
        O(probes), ~µs — rules/index.py): the connect-retry path runs
        inside event-loop failure callbacks and must never eat a
        synchronous device dispatch, least of all during a backend
        outage when retries spike."""
        m = self._matcher
        snap = m.snapshot()
        idx = m.index_snap(snap, hint)
        payload = m.snap_payload(snap)
        handles = payload if payload is not None else self.handles
        return handles[idx] if 0 <= idx < len(handles) else None

    def next_host(self, source_ip: bytes, hint: Optional[Hint] = None,
                  fam: Optional[str] = None,
                  exclude: Optional[set] = None) -> Optional[Connector]:
        """`next` semantics (hint group first, WRR fallback) with the
        classify served from the host index."""
        if hint is not None:
            h = self._search_host(hint)
            if h is not None:
                c = h.group.next(source_ip, fam, exclude)
                if c is not None:
                    return c
        return self._wrr_next(source_ip, fam, exclude)

    def seek_host(self, source_ip: bytes, hint: Hint,
                  fam: Optional[str] = None,
                  exclude: Optional[set] = None) -> Optional[Connector]:
        """`seek` semantics (hint-only, no WRR fallback), host index."""
        h = self._search_host(hint)
        if h is not None:
            return h.group.next(source_ip, fam, exclude)
        return None

    def next(self, source_ip: bytes, hint: Optional[Hint] = None,
             fam: Optional[str] = None,
             exclude: Optional[set] = None) -> Optional[Connector]:
        """exclude: ServerHandles a connect-retry must skip (the
        failure-containment layer re-enters this loop after a backend
        refused, excluding everything already tried)."""
        if hint is not None:
            c = self.seek(source_ip, hint, fam, exclude)
            if c is not None:
                return c
        return self._wrr_next(source_ip, fam, exclude)

    def _wrr_next(self, source_ip: bytes, fam: Optional[str],
                  exclude: Optional[set] = None) -> Optional[Connector]:
        with self._lock:
            seq, groups = self._wrr_seq, self._wrr_groups
            for _ in range(len(seq) + 1):
                if not seq:
                    return None
                idx = self._wrr_cursor % len(seq)
                self._wrr_cursor = idx + 1
                c = groups[seq[idx]].group.next(source_ip, fam, exclude)
                if c is not None:
                    return c
            return None

    # ------------------------------------------------- batched data plane

    def search_for_group_async(self, hint: Hint, cb, loop=None) -> None:
        """Async search_for_group via the ClassifyService micro-batch
        queue; cb(GroupHandle | None) fires on *loop*. The handle list
        arrives as the matcher generation's payload, so the index is
        always interpreted against the same add/remove generation that
        the device table encoded."""
        if not self.handles:
            cb(None)
            return
        from ..rules.service import ClassifyService

        def on_idx(idx: int, handles) -> None:
            cb(handles[idx] if handles and 0 <= idx < len(handles) else None)

        ClassifyService.get().submit_hint(self._matcher, hint, on_idx, loop)

    def next_async(self, source_ip: bytes, hint: Optional[Hint], cb,
                   fam: Optional[str] = None, loop=None) -> None:
        """Async `next`: the hint classify rides the ClassifyService
        micro-batch queue (rules/service.py) instead of a per-connection
        device dispatch; cb(Connector | None) fires on *loop*.

        This is the replacement for the reference's per-connection scan
        in Upstream.searchForGroup (Upstream.java:187-198).

        Span timers: the hint classify (submit->index) lands in the
        `classify` accept-stage histogram, the group/WRR selection in
        `backend_pick` (utils/metrics accept_stage_observe)."""
        if hint is None or not self.handles:
            t0 = time.monotonic()
            c = self._wrr_next(source_ip, fam)
            accept_stage_observe("backend_pick", time.monotonic() - t0)
            cb(c)
            return
        t_sub = time.monotonic()

        def on_idx(idx: int, handles, pick: int = -1, rows=None) -> None:
            t_idx = time.monotonic()
            accept_stage_observe("classify", t_idx - t_sub)
            if handles and 0 <= idx < len(handles):
                c = _picked(handles[idx], pick, rows) \
                    or handles[idx].group.next(source_ip, fam)
                if c is not None:
                    accept_stage_observe("backend_pick",
                                         time.monotonic() - t_idx)
                    cb(c)
                    return
            c = self._wrr_next(source_ip, fam)
            accept_stage_observe("backend_pick", time.monotonic() - t_idx)
            cb(c)

        self._submit(source_ip, hint, fam, on_idx, loop)

    def seek_async(self, source_ip: bytes, hint: Hint, cb,
                   fam: Optional[str] = None, loop=None) -> None:
        """Async `seek` (hint-only, no WRR fallback); cb(Connector|None)."""
        if not self.handles:
            cb(None)
            return

        def on_idx(idx: int, handles, pick: int = -1, rows=None) -> None:
            if handles and 0 <= idx < len(handles):
                cb(_picked(handles[idx], pick, rows)
                   or handles[idx].group.next(source_ip, fam))
            else:
                cb(None)

        self._submit(source_ip, hint, fam, on_idx, loop)

    def _submit(self, source_ip: bytes, hint: Hint, fam: Optional[str],
                on_idx, loop) -> None:
        """One classify submit for next_async / seek_async;
        on_idx(idx, handles[, pick, rows]). Where some group holds a
        pick table and the caller names no family (a family narrows the
        member set the table was built over), classify AND the matched
        group's pick ride one submit — on backend "jax" one launch."""
        from ..rules.service import ClassifyService
        svc = ClassifyService.get()
        if fam is not None or not self._picks.size():
            svc.submit_hint(self._matcher, hint, on_idx, loop)
            return

        def on_pick(idx: int, pick: int, payload) -> None:
            handles, rows = payload or (None, None)   # None: an error fill
            on_idx(idx, handles, pick, rows)

        svc.submit_classify_pick(self._pair, hint, source_ip, None,
                                 on_pick, loop)


def _picked(h: GroupHandle, pick: int, rows) -> Optional[Connector]:
    """The connector a device pick names: member `pick` of the list the
    group's row was built over, if the row is of the group's health
    generation NOW — the check `_source_next` makes on its own table —
    and the member still healthy; else None, and the caller asks the
    group. A row is installed behind its edge (bump and defer), so
    between a removal, a re-weighting or a health edge and the
    installer's publish the device's pick is a stale table's and is
    not used."""
    if pick < 0 or h.group.method != "source":
        return None
    row = rows.get(h.ref)
    if row is None or row[0] != h.group.health_version:
        return None
    servers = row[1]
    if pick >= len(servers) or not servers[pick].healthy:
        return None
    return Connector(servers[pick], h.group)
