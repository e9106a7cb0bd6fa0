"""Storm suite (tools/storm.py) — adversarial scenarios with SLO gates.

The tier-1 `storm` smoke runs a scaled-down flash crowd (~seconds,
structural assertions only — SLO differentials need full-scale load and
are asserted by the slow-marked full run). The stale-leader catch-up test covers
the cluster-plane fix the rolling-upgrade scenario forced: a restarted
lowest-id node must pull the fleet's state, not lead with its own
empty one.
"""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))


@pytest.mark.storm
def test_storm_smoke_flash_crowd():
    """Scaled-down flash crowd: both guard modes run end to end, the
    harness produces gate structures, nothing hard-fails."""
    import storm

    out = storm.scenario_flash_crowd(scale=0.08, seed=5)
    rows = out["rows"]
    for mode in ("static", "adaptive"):
        r = rows[mode]
        assert r["fail"] == 0, r        # no hard session failures
        assert set(r["slo"]) == {"p99_ms", "hard_failures",
                                 "served_rate", "crowd_in_top_clients"}
        # the analytics plane saw the crowd: the blaster's source is
        # the top client and the storm LB is attributed in top-routes
        assert r["slo"]["crowd_in_top_clients"]["pass"], r["top_clients"]
    assert rows["static"]["ok"] > 0
    ad = rows["adaptive"]
    assert ad["ok"] > 0
    # every attempt is accounted for: served or shed, never vanished
    assert ad["ok"] + ad["fail"] > 0 and ad["shed"] >= 0
    assert set(out["slo"]) == {"adaptive_passes", "differential"}


@pytest.mark.storm
def test_storm_smoke_replay_flash_crowd():
    """Scaled-down record-replay loop: a recorded crowd replays at 2x
    through a fresh world with zero hard failures, and the schedule
    hash is the same across both in-scenario builds."""
    import storm

    out = storm.scenario_replay_flash_crowd(scale=0.3, seed=5)
    assert out["recorded"]["fail"] == 0
    assert out["replay"]["speed"] == 2.0
    assert out["slo"]["hard_failures"]["pass"], out
    assert out["slo"]["schedule_deterministic"]["pass"], out
    assert len(out["schedule_hash"]) == 64
    # every replayed session is accounted for: served or shed
    assert out["replay"]["ok"] + out["replay"]["shed"] + \
        out["replay"]["fail"] > 0
    assert out["pass"], out["slo"]


@pytest.mark.storm
def test_storm_smoke_adversarial_crowd():
    """Scaled-down policing acceptance: the replayed legit mix holds
    its SLO while the herd is shed and ATTRIBUTED, the shed receipt is
    seed-deterministic, and the OFF differential is demonstrated or
    machine-honestly waived (the flash-crowd headroom rule)."""
    import storm
    from vproxy_tpu.utils import sketch

    if not sketch.enabled():
        pytest.skip("analytics sketches disabled")
    out = storm.scenario_adversarial_crowd(scale=0.25, seed=5)
    on = out["rows"]["on"]
    assert on["legit"]["fail"] == 0, on
    assert on["herd"]["attempts"] > 0
    # enforcement, not accident: the sheds carry policing attribution
    assert on["policed_sheds"] >= 0.9 * on["herd"]["shed"], on
    assert set(out["slo"]) == {"legit_slo_on", "herd_rejected",
                               "herd_attributed",
                               "receipt_deterministic", "differential"}
    assert out["slo"]["herd_rejected"]["value"] >= 0.90, out["slo"]
    assert out["slo"]["receipt_deterministic"]["pass"], out
    assert len(out["determinism_receipt"]) == 16
    assert out["pass"], out["slo"]


D15 = ("ROADMAP D15: a follower that refuses a stale leader's backward "
       "generation heals by a full snapshot of the generation it already "
       "holds, which tears its serving graph down and replays it")


def _two_nodes_with_u0():
    """A 2-node fleet whose leader (node 0) holds upstream u0 with four
    server-groups, replicated to node 1. -> (spec, apps, nodes, gen)"""
    import _fleetlib
    from vproxy_tpu.control.command import Command

    spec = _fleetlib.cluster_spec(2)
    apps, nodes = zip(*[_fleetlib.make_node(i, spec, hb_ms=250,
                                            poll_ms=100)
                        for i in range(2)])
    apps, nodes = list(apps), list(nodes)
    try:
        assert _fleetlib.wait_for(
            lambda: all(n.membership.peers_up() == 2 for n in nodes))
        Command.execute(apps[0], "add upstream u0")
        for i in range(4):
            Command.execute(
                apps[0], f"add server-group g{i} timeout 500 "
                "period 60000 up 1 down 2 annotations "
                f'{{"vproxy/hint-host":"s{i}.roll.example"}}')
            Command.execute(
                apps[0], f"add server-group g{i} to upstream u0 weight 10")
        gen = nodes[0].replicator.generation
        assert gen > 0
        assert _fleetlib.wait_for(
            lambda: nodes[1].replicator.generation == gen)
    except BaseException:
        _fleetlib.close_fleet(nodes, apps)
        raise
    return spec, apps, nodes, gen


def _count_teardowns(replicator) -> list:
    """Record every snapshot teardown of this replicator's graph."""
    seen = []
    teardown = replicator._teardown

    def counted():
        seen.append(time.monotonic())
        teardown()
    replicator._teardown = counted
    return seen


@pytest.mark.storm
def test_restarted_lowest_id_leader_catches_up_from_fleet():
    """The rolling-upgrade edge: node 0 (leader) dies and restarts
    EMPTY while the fleet is generations ahead. It must pull the
    fleet's state (heartbeat-advertised generations) instead of leading
    with — and replicating — its own empty config."""
    import _fleetlib

    spec, apps, nodes, gen = _two_nodes_with_u0()
    try:
        # kill the leader; node 1 now owns the only copy of the state
        nodes[0].close()
        apps[0].close()
        assert _fleetlib.wait_for(
            lambda: nodes[1].membership.leader_id() == 1, 15)
        torn_down = _count_teardowns(nodes[1].replicator)
        # restart node 0 EMPTY: leader by id, stale by state
        apps[0], nodes[0] = _fleetlib.make_node(0, spec, hb_ms=250,
                                                poll_ms=100)
        if not _fleetlib.wait_for(
                lambda: nodes[0].replicator.generation == gen
                and "u0" in apps[0].upstreams, 20):
            from vproxy_tpu.utils.events import FlightRecorder
            evs = [e for e in FlightRecorder.get().snapshot()
                   if e["kind"] in ("generation_reject",
                                    "generation_install")][-8:]
            peers = {p.node_id: (p.up, p.generation)
                     for p in nodes[0].membership.peer_list()}
            raise AssertionError(
                (nodes[0].replicator.generation, list(apps[0].upstreams),
                 nodes[0].membership.leader_id(), peers, evs))
        # and node 1 NEVER rolled back to the empty boot state
        assert nodes[1].replicator.generation == gen
        if not torn_down:
            assert "u0" in apps[1].upstreams
            assert len(apps[1].upstreams["u0"].handles) == 4
        assert _fleetlib.wait_for(
            lambda: len({n.replicator.checksum() for n in nodes}) == 1)
        assert nodes[1].replicator.generation == gen
        assert len(apps[1].upstreams["u0"].handles) == 4
        if torn_down:
            # node 1 polled the restarted leader before that one had
            # seen a heartbeat, refused its generation 0 and healed by a
            # snapshot of the generation it already held: its graph was
            # empty or part-built for tens of ms. The fleet converged,
            # but a serving follower must not do that
            pytest.xfail(f"{D15} ({len(torn_down)} teardown(s) of node "
                         "1 across node 0's restart)")
    finally:
        _fleetlib.close_fleet(nodes, apps)


@pytest.mark.storm
@pytest.mark.xfail(strict=True, reason=D15)
def test_follower_refusing_a_backward_generation_keeps_its_graph():
    """The race of the test above, made to happen: a healthy,
    fleet-confirmed follower hears a leader offer generation 0 (what a
    restarted lowest-id node serves before its first heartbeat tells it
    it is behind). It must refuse AND keep serving the graph it has:
    no teardown, the same Upstream object, four handles at every read."""
    import _fleetlib
    from vproxy_tpu.cluster.replicate import cluster_checksum
    from vproxy_tpu.control.app import Application

    spec, apps, nodes, gen = _two_nodes_with_u0()
    try:
        rep = nodes[1].replicator
        u0 = apps[1].upstreams["u0"]
        torn_down = _count_teardowns(rep)
        empty = Application(workers=1)
        boot = cluster_checksum(empty)
        empty.close()
        assert not rep.apply_frame(
            {"t": "snap", "gen": 0, "cksum": boot, "config": ""},
            leader_id=0)
        assert rep.generation == gen
        # the polls that follow, against a leader at the same generation
        partial = []

        def healed():
            up = apps[1].upstreams.get("u0")
            if up is not u0 or len(up.handles) != 4:
                partial.append(up)
            return not rep._force_snapshot
        assert _fleetlib.wait_for(healed, 10, poll=0.002)
        assert rep.generation == gen
        assert rep.checksum() == nodes[0].replicator.checksum()
        assert not torn_down, f"{len(torn_down)} teardown(s)"
        assert not partial
        assert apps[1].upstreams["u0"] is u0
    finally:
        _fleetlib.close_fleet(nodes, apps)


@pytest.mark.storm
def test_stale_leader_refuses_mutations_while_catching_up():
    """The catch-up window's write side: a restarted lowest-id node is
    leader by id but behind the fleet — a mutation accepted there would
    be journaled into a generation the catch-up snapshot is about to
    wipe (acknowledged, then silently lost). It must refuse until
    converged."""
    import _fleetlib
    from vproxy_tpu.control.command import CmdError, Command

    spec = _fleetlib.cluster_spec(2)
    apps, nodes = zip(*[_fleetlib.make_node(i, spec, hb_ms=250,
                                            poll_ms=100)
                        for i in range(2)])
    apps, nodes = list(apps), list(nodes)
    try:
        assert _fleetlib.wait_for(
            lambda: all(n.membership.peers_up() == 2 for n in nodes))
        Command.execute(apps[0], "add upstream u0")
        gen = nodes[0].replicator.generation
        assert gen > 0
        assert _fleetlib.wait_for(
            lambda: nodes[1].replicator.generation == gen)
        nodes[0].close()
        apps[0].close()
        assert _fleetlib.wait_for(
            lambda: nodes[1].membership.leader_id() == 1, 15)
        # restart node 0 EMPTY with its poll thread parked (huge
        # poll_ms): the catch-up window stays open deterministically
        apps[0], nodes[0] = _fleetlib.make_node(0, spec, hb_ms=250,
                                                poll_ms=600_000)
        assert _fleetlib.wait_for(
            lambda: nodes[0].replicator._fleet_ahead() is not None, 15)
        with pytest.raises(CmdError, match="behind the fleet"):
            Command.execute(apps[0], "add upstream u-lost")
        # manual catch-up (the poll thread is parked) -> mutations flow
        assert _fleetlib.wait_for(
            lambda: (nodes[0].replicator.sync_once() or True)
            and nodes[0].replicator.generation == gen, 15)
        Command.execute(apps[0], "add upstream u-after")
        assert "u-lost" not in apps[0].upstreams
        assert "u-after" in apps[0].upstreams
    finally:
        _fleetlib.close_fleet(nodes, apps)


@pytest.mark.storm
def test_fleet_snapshot_discard_of_unconfirmed_generations_is_loud():
    """The residue of the catch-up race the mutation gate cannot close:
    a restarted node cannot SEE the fleet it is behind until heartbeats
    converge, so a write accepted in that blind window is discarded by
    the catch-up snapshot — and the discard must be loud
    (generation_discard event), never silent."""
    import _fleetlib
    from vproxy_tpu.cluster.replicate import cluster_checksum
    from vproxy_tpu.control.app import Application
    from vproxy_tpu.control.command import Command
    from vproxy_tpu.utils.events import FlightRecorder

    spec = _fleetlib.cluster_spec(2)
    # lone node 0: leader by default (peer 1 never comes up), so its
    # journal is exactly the never-fleet-confirmed state
    app, node = _fleetlib.make_node(0, spec, hb_ms=250, poll_ms=600_000)
    try:
        Command.execute(app, "add upstream u-blind")
        assert node.replicator.journal
        assert not node.replicator._fleet_confirmed
        empty = Application(workers=1)
        want = cluster_checksum(empty)
        empty.close()
        # the fleet's (empty-state) snapshot arrives at a higher gen
        assert node.replicator.apply_frame(
            {"t": "snap", "gen": 7, "cksum": want, "config": ""})
        kinds = [e["kind"] for e in FlightRecorder.get().snapshot()]
        assert "generation_discard" in kinds
        assert "u-blind" not in app.upstreams
        assert node.replicator.generation == 7
    finally:
        _fleetlib.close_fleet([node], [app])


@pytest.mark.storm
@pytest.mark.slow
def test_storm_full_suite():
    """The real thing: every scenario at full scale, every SLO gate
    green, and the flash-crowd differential proved (static FAILS the
    p99 gate adaptive passes, at identical load)."""
    import storm

    rep = storm.run_all(seed=1, scale=1.0)
    bad = {k: v.get("slo", v.get("error"))
           for k, v in rep["scenarios"].items()
           if not v.get("skipped") and not v.get("pass")}
    assert rep["pass"], bad
    fc = rep["scenarios"]["flash_crowd"]
    assert fc["rows"]["static"]["slo"]["p99_ms"]["pass"] is False
    assert fc["rows"]["adaptive"]["pass"] is True
    ru = rep["scenarios"]["rolling_upgrade"]
    assert ru["generation_rejects"] >= 1 and ru["converged"]
