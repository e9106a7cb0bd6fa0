"""`correct` has to be able to come out false.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

At toy size on the CPU, through run.py's own path with only the look
for a chip skipped: the control of each cell (the reference with one
stated guarantee broken, in the program's place: a stale generation for
hints and routes, the port range ignored for ACL lookups), and the timed path
broken underneath for each fault these cells can have — an answer
altered where it is produced; a batch answered by the host failover
instead of the device (right answers, wrong server); a batch the
dispatcher could not serve (`-1` to every query of it). A step that
returns its state unchanged, half a batch left out of a mean and an
exchange between chips left out have nothing to break here: the cells
keep no training state and take one chip.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

import run  # noqa: E402
from selftest import TOY  # noqa: E402  (the toy sizes of the rehearsal)

CELLS = [w["name"] for w in
         run.load_json(run.ROOT, "BENCHMARK.json")["workloads"]]
# the faults below are planted in a ClassifyService; a cell whose driver
# brings its own service has its own (test_burst_cell.py)
SERVED = [w["name"] for w in
          run.load_json(run.ROOT, "BENCHMARK.json")["workloads"]
          if run.load_json(run.HERE, "traffic", w["traffic"] + ".json")
          ["driver"] == "classify_closed_loop"]


def toy(cell: str, seed: int, **kw) -> dict:
    return run.run_cell(cell, seed, 1.0, False, require_tpu=False,
                        overrides=TOY, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = toy(cell, 11)
    assert r["correct"] and r["failed"] == 0
    assert all(c["value"] == 0 for c in r["compared"].values())


@pytest.mark.parametrize("seed", [21, 22, 23])
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, seed):
    r = toy(cell, seed, control=True)
    assert not r["correct"]
    assert r["compared"]["wrong_verdicts"]["value"] > 0
    assert r["failed"] > 0


@pytest.mark.parametrize("cell", SERVED)
def test_altered_answer_is_not_correct(cell):
    import numpy as np

    def plant(svc):
        deliver, seen = svc._deliver, [0]

        def altered(reqs, idxs, *a, **kw):
            seen[0] += 1
            if seen[0] == 40:       # one verdict of one batch, mid-window
                idxs = np.array(idxs).copy()
                idxs[0] = idxs[0] + 1
            return deliver(reqs, idxs, *a, **kw)
        svc._deliver = altered

    r = toy(cell, 31, before_window=plant)
    assert not r["correct"]
    assert r["compared"]["wrong_verdicts"]["value"] >= 1


@pytest.mark.parametrize("cell", SERVED)
def test_host_failover_is_not_correct(cell):
    from vproxy_tpu.utils import failpoint

    def plant(_svc):
        failpoint.arm("device.dispatch.error", count=1)

    try:
        r = toy(cell, 41, before_window=plant)
    finally:
        failpoint.clear()
    c = r["compared"]
    assert not r["correct"]
    assert c["wrong_verdicts"]["value"] == 0      # the oracle answers right
    assert c["failovers"]["value"] >= 1
    assert c["answered_by_host_oracle"]["value"] >= 1
    assert r["failed"] >= 1


def test_dispatcher_error_is_not_correct():
    def plant(svc):
        begin, seen = svc._begin_uniform, [0]

        def broken(kind, matcher, reqs):
            seen[0] += 1
            if seen[0] == 30:
                raise ValueError("planted dispatcher fault")
            return begin(kind, matcher, reqs)
        svc._begin_uniform = broken

    r = toy(CELLS[0], 51, before_window=plant)
    assert not r["correct"]
    assert r["compared"]["wrong_verdicts"]["value"] >= 1
