"""REAL 2-process localhost cluster (the subprocess pattern of
test_multihost.py): two coordinator-connected jax processes each boot a
full ClusterNode over real UDP/TCP and prove the cluster plane
end-to-end —

* membership converges (both peers UP, node 0 elected leader, the
  cluster node id IS the jax dist process id);
* rule updates issued on the leader replicate through the
  generation-tagged command log; the follower's install is gated on
  the engine-table checksum, and both hosts print their checksum at
  the final generation for a cross-process equality assert;
* step-synchronized dispatch answers oracle-parity verdicts under
  deliberately UNEQUAL per-host load (40 vs 6 queries — the idle host
  contributes empty padded batches, steps stay in lockstep over the
  cross-process UDP barrier);
* killing node 1 mid-run drives the survivor through the
  barrier-timeout degrade edge (timeout < membership down-detection,
  so the stall fires first): every in-flight and subsequent query is
  answered from the inline host-index path — not one failed query.
"""
import os
import re
import subprocess
import sys

import pytest

_WORKER = r"""
import os, socket, sys, threading, time
pid = int(sys.argv[1])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.environ["VPROXY_REPO"])

from vproxy_tpu.parallel import mesh as M
ok = M.init_distributed(f"127.0.0.1:{os.environ['COORD_PORT']}",
                        num_processes=2, process_id=pid)
assert ok
import jax
assert jax.process_count() == 2
# initialize the CPU backend ON THE MAIN THREAD before any cluster
# thread touches a device: the distributed topology exchange behind
# backend init is not safe to race from the replication + dispatch
# threads (ALREADY_EXISTS on the coordination-service key)
assert len(jax.devices()) == 8

from vproxy_tpu.cluster import ClusterNode, parse_peers, self_node_id
from vproxy_tpu.control.app import Application
from vproxy_tpu.control.command import Command
from vproxy_tpu.rules import oracle

assert self_node_id() == pid  # cluster id IS the dist process id

def wait_for(pred, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return pred()

app = Application(workers=1)
# hb 500ms x down 3 = 1500ms down-detection, ABOVE the 1200ms barrier
# timeout: killing a peer must hit the barrier-timeout degrade edge
# first, not the membership eviction
node = ClusterNode(app, pid, parse_peers(os.environ["CLUSTER_SPEC"]),
                   hb_ms=500, poll_ms=200)
app.cluster = node
node.membership.start()
node.replicator.start()

# ---- the control sync channel (test harness only, not cluster code)
if pid == 0:
    sync_srv = socket.socket()
    sync_srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    sync_srv.bind(("127.0.0.1", int(os.environ["SYNC_PORT"])))
    sync_srv.listen(1)
    sync, _ = sync_srv.accept()
else:
    sync = None
    for _ in range(100):
        try:
            sync = socket.create_connection(
                ("127.0.0.1", int(os.environ["SYNC_PORT"])), timeout=2)
            break
        except OSError:
            time.sleep(0.2)
    assert sync is not None, "sync channel never connected"
sync.settimeout(120)

# ---- membership converges, node 0 leads
assert wait_for(lambda: node.membership.peers_up() == 2), \
    "membership never converged"
assert node.membership.leader_id() == 0
print(f"MEMBER_OK pid={pid} peers=2 leader=0", flush=True)

# ---- leader mutations replicate; install is checksum-gated
N_GROUPS = 12
if pid == 0:
    Command.execute(app, "add upstream u0")
    for i in range(N_GROUPS):
        Command.execute(
            app, f"add server-group g{i} timeout 500 period 60000 up 1 "
            f'down 2 annotations {{"vproxy/hint-host":"s{i}.corp.example"}}')
        Command.execute(app, f"add server-group g{i} to upstream u0 "
                        f"weight 10")
gen1 = 1 + 2 * N_GROUPS
# >= : a fresh follower's snapshot sync may jump straight to the
# newest generation rather than land on every intermediate one
assert wait_for(lambda: node.replicator.generation >= gen1), \
    f"pid={pid} stuck at {node.replicator.status()}"
# a further rule UPDATE on the leader replicates to the new generation
if pid == 0:
    Command.execute(app, 'update server-group g3 annotations '
                    '{"vproxy/hint-host":"swapped.corp.example"}')
gen2 = gen1 + 1
assert wait_for(lambda: node.replicator.generation == gen2), \
    f"pid={pid} stuck at {node.replicator.status()}"
assert node.replicator.generation_lag() == 0
# both processes print the checksum at the SAME generation; the parent
# asserts cross-process equality (install was already gated on it)
print(f"CKSUM pid={pid} gen={node.replicator.generation} "
      f"val={node.replicator.checksum():#010x}", flush=True)

# ---- step-synchronized dispatch, deliberately unequal per-host load
ups = app.upstreams["u0"]
rules = [h.merged_rule() for h in ups.handles]
assert len(rules) == N_GROUPS
matcher = ups._matcher  # the replicated generation's engine table
loop = node.attach_submit(matcher, step_ms=50, batch_cap=8,
                          timeout_ms=1200)

def classify_all(n, stride):
    got, done = [], threading.Event()
    for q in range(n):
        from vproxy_tpu.rules.ir import Hint
        h = Hint(host=f"s{(q * stride) % (N_GROUPS + 2)}.corp.example")
        def cb(idx, payload, h=h):
            got.append((h, idx))
            if len(got) >= n:
                done.set()
        loop.submit(h, cb)
    assert done.wait(60), f"pid={pid}: {len(got)}/{n} answers"
    for h, idx in got:
        want = oracle.search(rules, h)
        assert idx == want, (pid, h, idx, want)
    return got

classify_all(40 if pid == 0 else 6, stride=3 if pid == 0 else 5)
assert not loop.degraded, "phase A must stay step-synchronized"
# the near-idle host keeps stepping empty padded batches on the shared
# clock — steps advance even with nothing queued
assert wait_for(lambda: loop.steps_total >= 3, timeout=10)
assert not loop.degraded
print(f"STEP_OK pid={pid} steps={loop.steps_total}", flush=True)

# ---- kill node 1 mid-run; node 0 degrades through the barrier timeout
if pid == 1:
    sync.sendall(b"A-done\n")
    assert sync.recv(16)  # "die"
    print(f"DIST_OK pid=1 exiting mid-run", flush=True)
    sys.stdout.flush()
    os._exit(0)

assert sync.recv(16)  # node 1 finished phase A
sync.sendall(b"die\n")
# queries land WHILE the peer dies: the stall must not fail any of them
got = classify_all(10, stride=7)
assert wait_for(lambda: loop.degraded, timeout=30), \
    "survivor never degraded after peer death"
assert loop.barrier_stalls >= 1
print(f"DIST_OK pid=0 degraded stalls={loop.barrier_stalls} "
      f"answers={len(got)}", flush=True)
sys.stdout.flush()
os._exit(0)
"""


_PORT_TAKEN = re.compile(
    r"address already in use|failed to add port to server", re.I)


@pytest.mark.timeout(180)
def test_real_two_process_cluster(tmp_path):
    """Spawns two coordinator-connected jax processes, each a full
    ClusterNode over real localhost UDP/TCP; see module docstring."""
    import socket

    def free_port(kind=socket.SOCK_STREAM):
        s = socket.socket(socket.AF_INET, kind)
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")
           and not k.startswith("VPROXY_TPU_CLUSTER")}
    env["VPROXY_REPO"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    # a probed port is free only until someone else binds it: a sibling
    # xdist worker can take it before the coordinator does (as in
    # test_multihost). Retry the whole bring-up on fresh ports.
    for _attempt in range(3):
        coord = free_port()
        sync = free_port()
        hb = [free_port(socket.SOCK_DGRAM) for _ in range(2)]
        repl = [free_port() for _ in range(2)]
        env["COORD_PORT"] = str(coord)
        env["SYNC_PORT"] = str(sync)
        env["CLUSTER_SPEC"] = (f"127.0.0.1:{hb[0]}/{repl[0]},"
                               f"127.0.0.1:{hb[1]}/{repl[1]}")
        procs = [subprocess.Popen(
            [sys.executable, str(script), str(pid)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            for pid in (0, 1)]
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=150)
                outs.append(out.decode())
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        if not any(p.returncode and _PORT_TAKEN.search(out)
                   for p, out in zip(procs, outs)):
            break
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-4000:]}"
        assert f"MEMBER_OK pid={pid}" in out, out[-2000:]
        assert f"STEP_OK pid={pid}" in out, out[-2000:]
        assert f"DIST_OK pid={pid}" in out, out[-2000:]
    # cross-process: both hosts reported the SAME checksum at the SAME
    # generation (each install was already gated on the leader's value)
    sums = {}
    for out in outs:
        m = re.search(r"CKSUM pid=(\d) gen=(\d+) val=(0x[0-9a-f]+)", out)
        assert m, out[-2000:]
        sums[m.group(1)] = (m.group(2), m.group(3))
    assert sums["0"] == sums["1"], sums
    assert "degraded stalls=" in outs[0]
