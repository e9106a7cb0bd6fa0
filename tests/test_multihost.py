"""Multi-host mesh: (host, batch, rules) layouts + jax.distributed.

Two levels of evidence:

* single-process SIMULATION — an 8-virtual-device mesh shaped
  (2 hosts × 2 batch × 2 rules): tables replicated over "host", rules
  sharded within a host, queries over (host, batch). The production
  jax-fp-sharded engine must answer bit-for-bit like the oracle.
* REAL process-count>1 — two subprocesses bring up
  jax.distributed.initialize over a localhost coordinator (4 virtual
  CPU devices each = 8 global), build the same host mesh across the
  process boundary, and run the sharded fp classify with every process
  contributing its OWN local query slice
  (make_array_from_process_local_data); each asserts oracle parity on
  its local results. This exercises the exact code path a 2-host TPU
  pod slice would run, with DCN standing in for the coordinator.
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from vproxy_tpu.parallel import mesh as M
from vproxy_tpu.rules import oracle
from vproxy_tpu.rules.engine import CidrMatcher, HintMatcher
from vproxy_tpu.rules.ir import AclRule, Hint, HintRule, Proto
from vproxy_tpu.utils.ip import Network, mask_bytes


def mk_world(n_rules=300, n_acl=64, batch=64):
    rules = [HintRule(host=f"s{i}.ns{i % 13}.corp.example")
             for i in range(n_rules)]
    acls = []
    for i in range(n_acl):
        m = mask_bytes(8 + (i % 24))
        ip = bytes([10, i % 4, (i * 7) % 256, 0])
        acls.append(AclRule(
            f"a{i}", Network(bytes(np.frombuffer(ip, np.uint8) &
                                   np.frombuffer(m, np.uint8)), m),
            Proto.TCP, (i * 11) % 50000, (i * 11) % 50000 + 2000,
            i % 2 == 0))
    hints = [Hint.of_host(f"s{(i * 17) % n_rules}.ns{((i * 17) % n_rules) % 13}"
                          f".corp.example") for i in range(batch)]
    addrs = [bytes([10, i % 4, (i * 3) % 256, i % 256])
             for i in range(batch)]
    ports = [(i * 11) % 50000 + 100 for i in range(batch)]
    return rules, acls, hints, addrs, ports


def test_host_mesh_simulated_2x2x2():
    mesh = M.make_mesh(8, batch=2, hosts=2)
    assert mesh.axis_names == ("host", "batch", "rules")
    assert M.batch_axes(mesh) == ("host", "batch")
    assert M.query_shards(mesh) == 4
    rules, acls, hints, addrs, ports = mk_world()
    hm = HintMatcher(rules, backend="jax-fp-sharded", mesh=mesh)
    am = CidrMatcher([a.network for a in acls], acl=acls,
                     backend="jax-fp-sharded", mesh=mesh)
    got_h = hm.match(hints)
    got_a = am.match(addrs, ports)
    for i in range(len(hints)):
        assert got_h[i] == oracle.search(rules, hints[i]), i
    for i in range(len(addrs)):
        want = next((j for j, a in enumerate(acls)
                     if a.network.contains_ip(addrs[i])
                     and a.min_port <= ports[i] <= a.max_port), -1)
        assert got_a[i] == want, i


def test_host_mesh_runtime_update_keeps_shapes():
    mesh = M.make_mesh(8, batch=2, hosts=2)
    rules, _, hints, _, _ = mk_world(n_rules=200)
    hm = HintMatcher(rules, backend="jax-fp-sharded", mesh=mesh)
    assert hm.match(hints[:8])[0] == oracle.search(rules, hints[0])
    rules2 = list(rules)
    rules2[17] = HintRule(host="swapped.corp.example")
    hm.set_rules(rules2)
    assert hm.match([Hint.of_host("swapped.corp.example")])[0] == 17


_WORKER = r"""
import os, sys
pid = int(sys.argv[1])
port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.environ["VPROXY_REPO"])
from vproxy_tpu.parallel import mesh as M
ok = M.init_distributed(f"127.0.0.1:{port}", num_processes=2,
                        process_id=pid)
assert ok
import jax
import numpy as np
assert jax.process_count() == 2
assert len(jax.devices()) == 8
sys.path.insert(0, os.path.join(os.environ["VPROXY_REPO"], "tests"))
from test_multihost import mk_world
from vproxy_tpu.ops import fphash as F
from vproxy_tpu.ops import tables as T
from vproxy_tpu.rules import oracle

mesh = M.make_mesh(8, batch=1, hosts=2)  # host axis = process boundary
rules, _, hints, _, _ = mk_world(batch=64)
B_local = 32  # each process contributes ITS OWN half of the batch
my_hints = hints[pid * B_local:(pid + 1) * B_local]

stab = F.compile_hint_fp_sharded(rules, mesh.shape["rules"])
dev = M.shard_hash_table(stab, mesh)
q = F.encode_hint_queries_fp_sharded(my_hints, stab)
qd = M.shard_hint_queries_sharded(q, mesh)
fn = M.make_sharded_hint_fn(
    mesh, {k: v.ndim for k, v in stab.arrays.items()},
    {k: v.ndim for k, v in q.items()}, kernel=F.hint_fp_match)
out = fn(dev, qd, np.int32(stab.shard_size))
local = M.to_local(out)
assert local.shape[0] == B_local, local.shape
for i, h in enumerate(my_hints):
    want = oracle.search(rules, h)
    assert local[i] == want, (pid, i, int(local[i]), want)
print(f"DIST_OK pid={pid} parity on {B_local} local queries", flush=True)
"""


_PORT_TAKEN = re.compile(
    r"address already in use|failed to add port to server", re.I)


@pytest.mark.timeout(180)
def test_real_two_process_distributed(tmp_path):
    """Spawns two coordinator-connected jax processes; each runs the
    sharded fp classify over the cross-process host mesh with its own
    local query slice and checks oracle parity (cross-process CPU
    collectives ride jax's default gloo implementation)."""
    import socket
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")}
    env["VPROXY_REPO"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    # jax.distributed takes an address, not a socket, so the probed port
    # is free only until someone else binds it: a sibling xdist worker
    # can take it before the coordinator does. Retry on a fresh port.
    for _attempt in range(3):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        procs = [subprocess.Popen(
            [sys.executable, str(script), str(pid), str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
            for pid in (0, 1)]
        outs = []
        try:
            for p in procs:  # worker 0 hosts the coordinator
                out, _ = p.communicate(timeout=150)
                outs.append(out.decode())
                if _PORT_TAKEN.search(outs[0]):
                    break
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        if len(outs) == 2:
            break
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-3000:]}"
        assert f"DIST_OK pid={pid}" in out, out[-2000:]
