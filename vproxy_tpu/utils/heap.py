"""The interpreter's collector and the rule heap: what is alive at a
publish, and at the dispatcher's start, is frozen out of its reach.

CPython runs a full collection when the objects promoted to the old
generation since the last one pass a quarter of what it held. Under
load the promoted objects are the lookups in flight (a request lives
longer than two young collections); what the old generation holds is
the rule heap — rules, host indexes, listeners, groups — which never
dies. So every second or so the collector stops every thread to walk a
third of a million objects that cannot be garbage. `gc.freeze()` moves
everything alive into a permanent generation no collection examines;
a frozen object still dies by reference count like any other (the
tables a swap retired, a delivered request).

Two events call `settle()`, the ones after which the heap just built
is long-lived: a generation publish (`rules/engine.TableInstaller`)
and the classify dispatcher's start (`rules/service.ClassifyService`).
Each collects the young generations first — cheap, bounded by their
thresholds — so young cyclic garbage is not frozen alive.

A frozen *cycle* that becomes garbage later is never examined again,
so the population is bounded. `_base` is the freeze count of the
settled heap: right after the last full examination, or at the
dispatcher's start, where bring-up ends (tables, listeners and the
callers' closures all grow the count before it, and none of that is
garbage). When a freeze leaves the count `FACTOR`-fold over it, the
next publish unfreezes everything for one full collection. An idle
publish runs that collection itself; one under load must not stop
every thread for it — no generation-2 `gc.collect()` while lookups
are served — so it leaves the collector's own next full collection to
examine the heap once, and the hook below freezes again as that ends.

Always on /metrics: `vproxy_runtime_heap_frozen_objects`,
`vproxy_runtime_heap_freezes_total{event}`,
`vproxy_runtime_heap_reexaminations_total` (docs/observability.md).
"""
from __future__ import annotations

import gc
import threading

EVENTS = ("publish", "serve_start")
FACTOR = 2      # frozen population over `_base` that asks for a full look

_lock = threading.Lock()
_base = 0           # freeze count of the settled heap (see above)
_last = 0           # freeze count right after the last freeze
_thawed = False     # unfrozen, the collector's next full collection due
_freezes = dict.fromkeys(EVENTS, 0)
_reexaminations = 0


def settle(event: str, idle: bool) -> None:
    """Freeze what is alive now. event: one of EVENTS. idle: no lookup
    was served lately, so a full collection here delays nobody."""
    global _base, _last, _thawed, _reexaminations
    start = event == "serve_start"
    with _lock:
        if not (start or _thawed) and _last >= FACTOR * _base > 0:
            gc.unfreeze()
            _reexaminations += 1
            _thawed = True
            if _on_collection not in gc.callbacks:
                gc.callbacks.append(_on_collection)
        if _thawed and not idle:
            return      # _on_collection freezes, after the examination
        gc.collect(2 if _thawed else 1)
        gc.freeze()
        _freezes[event] += 1
        _last = gc.get_freeze_count()   # walks the frozen list: once
        if _thawed or start or not _base:
            _base = _last
        _thawed = False


def _on_collection(phase: str, info: dict) -> None:
    """gc.callbacks hook, installed at the first re-examination: the
    collector's own full collection has just examined the thawed heap.
    The lock is only tried: a collection can begin inside settle()."""
    global _base, _last, _thawed
    if _thawed and phase == "stop" and info["generation"] == 2 \
            and _lock.acquire(blocking=False):
        try:
            if _thawed:
                gc.freeze()
                _base = _last = gc.get_freeze_count()
                _thawed = False
        finally:
            _lock.release()


def frozen_objects() -> int:
    return gc.get_freeze_count()


def freezes_total(event: str) -> int:
    return _freezes[event]


def reexaminations_total() -> int:
    return _reexaminations
