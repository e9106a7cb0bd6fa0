"""The control: the reference, put in the program's place, with one of
the configuration's guarantees broken.

The configurations state no precision; they state that every verdict is
the exact first match of the published generation. The control answers
through the same submit_* entry the window drives, at the cell's own
size and load, from the plain reference made to break that guarantee
the way a later PR would be tempted to: `stale` serves a generation in
which 1 % of the hint rules or routes are still the old ones (an
install that has not reached the device, a cache that outlives it);
`noport` answers an ACL lookup by the address alone, the port range
ignored. `correct` has to come out false (tests/test_correct.py keeps it
at toy size; PERF.md has the chip runs).
"""
from __future__ import annotations

import queue
import threading


class _Stats:
    def __init__(self):
        self.queries = self.dispatches = self.device_queries = 0
        self.oracle_queries = self.failovers = self.max_batch = 0
        self.inline_fast = self.budget_reroutes = 0
        self.last_failover = ""


class ControlService:
    def __init__(self, dep, plan, seed: int):
        self.stats = _Stats()
        kinds = dict.fromkeys(plan.traffic["kinds"])
        self.what = ", ".join(f"{k}: {dep.controls[k]}" for k in kinds)
        broken = dep.answers(plan.pool, control=True, seed=seed).tolist()
        self._answers = [tuple(a) if dep.has_pick(k) else (a[0],)
                         for a, (k, _q) in zip(broken, plan.pool)]
        self._rank = {self._key(k, q): r
                      for r, (k, q) in enumerate(plan.pool)}
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="control-dispatch")
        self._thread.start()

    @staticmethod
    def _key(kind: str, q: tuple) -> tuple:
        if kind in ("hint", "cpick"):
            return (kind,) + tuple(q)
        return (kind, q[0], q[1] if len(q) > 1 else None)

    def submit_hint(self, _m, hint, cb, loop=None) -> None:
        self._q.put((("hint", hint.host, hint.port, hint.uri), cb))

    def submit_cidr(self, _m, addr, port, cb, loop=None) -> None:
        self._q.put((("route" if port is None else "acl", addr, port), cb))

    def submit_classify_pick(self, _m, hint, ip, port, cb, loop=None) -> None:
        self._q.put((("cpick", hint.host, hint.port, hint.uri, ip, port),
                     cb))

    def _run(self) -> None:
        st = self.stats
        while True:
            item = self._q.get()
            if item is None:
                return
            batch = [item]
            while not self._q.empty() and len(batch) < 4096:
                nxt = self._q.get()
                if nxt is None:
                    self._q.put(None)
                    break
                batch.append(nxt)
            st.queries += len(batch)
            st.dispatches += 1
            st.device_queries += len(batch)
            st.max_batch = max(st.max_batch, len(batch))
            for key, cb in batch:
                cb(*self._answers[self._rank[key]], None)

    def close(self) -> None:
        self._q.put(None)
        self._thread.join(5.0)
