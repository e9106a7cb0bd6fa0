"""Device-mesh sharding for the classify engine.

The scaling story (SURVEY.md §5 "distributed communication backend"):
rule tables live in HBM sharded over the mesh's "rules" axis (the
tensor-parallel analog — each chip holds a slice of every table and the
argmax/min reduction rides ICI collectives inserted by the SPMD
partitioner), while query micro-batches shard over "batch" (the
data-parallel analog — the per-core event-loop sharding of
app/Application.java:90-105 maps to batch shards).

Multi-host: init_distributed() brings up jax.distributed (the analog of
the reference's cross-host fabric, RemoteSwitchIface.java — but over
the accelerator DCN, not VXLAN), after which jax.devices() is GLOBAL
and make_mesh(hosts=N) lays out a (host, batch, rules) mesh where

* tables are REPLICATED across the "host" axis (each host holds the
  full rule set — updates are control-plane broadcasts over DCN),
* the "rules" shards stay WITHIN a host, so the winner pmax/pmin
  reductions ride ICI only,
* query batches shard over (host, batch): each host classifies its own
  accepted connections; no per-query DCN traffic at all.

put()/to_local() abstract single- vs multi-process array creation so
the same engine code runs on one process (device_put) or many
(make_array_from_process_local_data, every process contributing its
local batch slice).
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     timeout_s: Optional[float] = None) -> bool:
    """jax.distributed multi-host bring-up; reads VPROXY_TPU_DIST_COORD
    (host:port), VPROXY_TPU_DIST_NPROC, VPROXY_TPU_DIST_PROCID when the
    args are absent. Returns False (no-op) when not configured —
    single-host deployments never pay for it. Must run before the first
    device use (main.py boots it first thing).

    Bring-up is BOUNDED (VPROXY_TPU_DIST_TIMEOUT_S, default 120s): an
    unreachable coordinator, a missing peer, or two processes booted
    with the same VPROXY_TPU_DIST_PROCID would otherwise hang the
    barrier forever with no hint which knob is wrong. An unreachable
    coordinator is caught by a bounded pre-flight TCP probe and raises
    a RuntimeError naming the env vars to check BEFORE entering
    jaxlib's client (whose own deadline path is a LOG(FATAL) process
    abort — still bounded by initialization_timeout, just not
    catchable); other barrier failures surface through
    initialization_timeout."""
    coordinator = coordinator or os.environ.get("VPROXY_TPU_DIST_COORD")
    if num_processes is None:
        num_processes = int(os.environ.get("VPROXY_TPU_DIST_NPROC", "0")
                            or 0)
    if process_id is None:
        process_id = int(os.environ.get("VPROXY_TPU_DIST_PROCID", "-1")
                         or -1)
    if not coordinator or num_processes <= 1 or process_id < 0:
        return False
    if timeout_s is None:
        timeout_s = float(os.environ.get("VPROXY_TPU_DIST_TIMEOUT_S",
                                         "120"))
    if process_id > 0:
        _preflight_coordinator(coordinator, num_processes, process_id,
                               timeout_s)
    try:
        jax.distributed.initialize(
            coordinator, num_processes=num_processes,
            process_id=process_id,
            initialization_timeout=int(timeout_s))
    except Exception as e:
        raise RuntimeError(
            f"jax.distributed bring-up failed for process "
            f"{process_id}/{num_processes} against coordinator "
            f"{coordinator} within {timeout_s:.0f}s: {e!r}. Check "
            "VPROXY_TPU_DIST_COORD (is the coordinator host:port "
            "reachable, and running process id 0?), "
            "VPROXY_TPU_DIST_NPROC (are ALL processes booted?), and "
            "VPROXY_TPU_DIST_PROCID (ids must be unique in "
            f"[0, {num_processes})) — a duplicate or missing id leaves "
            "the bring-up barrier waiting forever; raise "
            "VPROXY_TPU_DIST_TIMEOUT_S for genuinely slow fleets."
        ) from e
    return True


def _preflight_coordinator(coordinator: str, num_processes: int,
                           process_id: int, timeout_s: float) -> None:
    """Bounded TCP probe of the coordinator before handing control to
    jaxlib: its deadline path aborts the process (LOG(FATAL)), so the
    by-far-most-common misconfiguration — coordinator address wrong or
    process 0 not up — must fail as a catchable error here instead."""
    import socket
    import time
    host, _, port = coordinator.rpartition(":")
    if host.startswith("[") and host.endswith("]"):
        host = host[1:-1]
    deadline = time.monotonic() + timeout_s
    last: Optional[Exception] = None
    while time.monotonic() < deadline:
        try:
            socket.create_connection(
                (host, int(port)),
                timeout=max(0.5, min(5.0, deadline - time.monotonic()))
            ).close()
            return
        except OSError as e:
            last = e
            time.sleep(min(1.0, max(0.05, deadline - time.monotonic())))
    raise RuntimeError(
        f"jax.distributed coordinator {coordinator} unreachable after "
        f"{timeout_s:.0f}s (process {process_id}/{num_processes}): "
        f"{last!r}. Check VPROXY_TPU_DIST_COORD (must be the host:port "
        "where the VPROXY_TPU_DIST_PROCID=0 process runs, and that "
        "process must be up first), VPROXY_TPU_DIST_NPROC, and that "
        "every process has a unique VPROXY_TPU_DIST_PROCID in "
        f"[0, {num_processes}); raise VPROXY_TPU_DIST_TIMEOUT_S for "
        "genuinely slow fleets.")


def make_mesh(n_devices: Optional[int] = None, batch: int = 1,
              hosts: int = 1) -> Mesh:
    """Mesh with axes (batch, rules) — or (host, batch, rules) when
    hosts > 1; "rules" gets the remaining devices. With hosts equal to
    jax.process_count() the host axis follows process boundaries
    (jax.devices() orders all of process 0's devices first)."""
    devs = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    n = len(devs)
    assert n % (batch * hosts) == 0, (n, batch, hosts)
    if hosts > 1:
        return Mesh(np.array(devs).reshape(hosts, batch,
                                           n // (batch * hosts)),
                    ("host", "batch", "rules"))
    return Mesh(np.array(devs).reshape(batch, n // batch), ("batch", "rules"))


def batch_axes(mesh: Mesh) -> tuple:
    """Every mesh axis except "rules" carries query batches."""
    return tuple(a for a in mesh.axis_names if a != "rules")


def query_shards(mesh: Mesh) -> int:
    """Total batch-axis size (the pad multiple for query batches)."""
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n


def put(mesh: Mesh, spec: P, local: np.ndarray):
    """Create a global array from this process's local data: device_put
    single-process; make_array_from_process_local_data when the mesh
    spans processes (each host contributes its own batch slice; table
    arrays — replicated or rules-sharded-within-host — pass the full
    array since every local shard is derivable from it)."""
    sh = NamedSharding(mesh, spec)
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(sh, local)
    return jax.device_put(local, sh)


def to_local(arr) -> np.ndarray:
    """This process's contiguous slice of a batch-sharded output (the
    whole array on a single process). Assumes the leading dim is the
    batch axis and this process's shards are contiguous in it (true for
    (host, batch, rules) meshes where host follows process order). An
    output replicated over the in-host "rules" axis has one shard COPY
    per device — dedupe by index so each slice contributes once."""
    if jax.process_count() <= 1:
        return np.asarray(arr)
    seen = {}
    for s in arr.addressable_shards:
        start = s.index[0].start or 0
        if start not in seen:
            seen[start] = s.data
    return np.concatenate(
        [np.asarray(seen[k]) for k in sorted(seen)])


# PartitionSpecs per table key: 2-D matmul weights shard on their rule
# column axis, 1-D metadata shards on axis 0.
_HINT_SPECS = {
    "host_w": P(None, "rules"), "host_c": P("rules"),
    "host_valid": P("rules", None), "host_wild": P("rules"),
    "uri_w": P(None, "rules"), "uri_c": P("rules"),
    "uri_valid": P("rules"), "uri_wild": P("rules"),
    "uri_score": P("rules"), "port": P("rules"), "active": P("rules"),
}
_CIDR_SPECS = {
    "w": P(None, "rules"), "c": P("rules"), "family": P("rules"),
    "valid": P("rules"), "min_port": P("rules"), "max_port": P("rules"),
    "allow": P("rules"),
}
_HINT_Q_SPECS = {
    "host": P("batch", None), "has_host": P("batch"), "uri": P("batch", None),
    "has_uri": P("batch"), "port": P("batch"),
}


def shard_hint_table(table: dict, mesh: Mesh) -> dict:
    return {k: jax.device_put(v, NamedSharding(mesh, _HINT_SPECS[k]))
            for k, v in table.items()}


def shard_cidr_table(table: dict, mesh: Mesh) -> dict:
    return {k: jax.device_put(v, NamedSharding(mesh, _CIDR_SPECS[k]))
            for k, v in table.items()}


def shard_hint_queries(q: dict, mesh: Mesh) -> dict:
    return {k: jax.device_put(v, NamedSharding(mesh, _HINT_Q_SPECS[k]))
            for k, v in q.items()}  # dense experimental path: 2-axis mesh


def shard_addr_queries(addr: np.ndarray, fam: np.ndarray, mesh: Mesh,
                       port: Optional[np.ndarray] = None):
    ba = batch_axes(mesh)
    arrs = {"a": addr, "f": fam}
    specs = {"a": P(ba, None), "f": P(ba)}
    if port is not None:
        arrs["p"] = port
        specs["p"] = P(ba)
    out = put_many(mesh, specs, arrs)
    return out["a"], out["f"], out.get("p")


# ------------------------------------------------- hash-path (production)
#
# The cuckoo-hash tables (ops/hashmatch, "the 10M matches/s path") shard
# by SLICING THE RULE LIST: ShardedHashTable stacks S per-shard compiled
# tables on a leading axis that carries the "rules" PartitionSpec, and
# each device runs the unchanged single-shard kernel on its local slice
# under shard_map. The global winner is a two-phase collective: pmax of
# the match level, then pmin of the global rule index among the level
# winners — Upstream.java:187's strictly-greater-max/earliest-tie
# semantics as an ICI reduction. CIDR first-match reduces with one pmin.


def _leading_rules_spec(arrays: dict) -> dict:
    return {k: P("rules", *([None] * (v.ndim - 1)))
            for k, v in arrays.items()}


def shard_hash_table(stab, mesh: Mesh) -> dict:
    """Ship a ShardedHashTable's stacked arrays over the mesh (tables
    replicate across host/batch axes; multi-process hosts each pass the
    identical full array). Paced per key (ops.cuckoo.coop_yield): a
    standby install's upload slices multi-MB arrays per device under
    the GIL — unpaced, that window alone shows up in serving p99."""
    from ..ops.cuckoo import coop_yield
    specs = _leading_rules_spec(stab.arrays)
    out = {}
    for k, v in stab.arrays.items():
        coop_yield()
        out[k] = put(mesh, specs[k], v)
    return out


def release_host(stab) -> None:
    """Drop a ShardedHashTable's stacked HOST arrays after the device
    upload (the standby-swap memory-lean contract): each array is
    replaced by a zero-size stub that preserves ndim/dtype, which is
    all the jitted-fn spec builders ({k: v.ndim}) ever read. A 1M-rule
    generation would otherwise live in host RAM for as long as the
    matcher keeps its published snapshot."""
    stab.arrays = {k: np.empty((0,) * v.ndim, v.dtype)
                   for k, v in stab.arrays.items()}


def put_many(mesh: Mesh, specs: dict, arrs: dict) -> dict:
    """Batched device_put of a query/table dict: ONE call ships every
    array (the per-key call paid measurable per-transfer overhead on
    the dispatch path). Falls back to per-key put on multi-process
    meshes (make_array_from_process_local_data is per-array) or when
    the runtime rejects the batched form."""
    keys = list(arrs)
    if jax.process_count() > 1:
        return {k: put(mesh, specs[k], arrs[k]) for k in keys}
    try:
        out = jax.device_put(
            [arrs[k] for k in keys],
            [NamedSharding(mesh, specs[k]) for k in keys])
        return dict(zip(keys, out))
    except (TypeError, ValueError):
        return {k: put(mesh, specs[k], arrs[k]) for k in keys}


def shard_hint_queries_sharded(q: dict, mesh: Mesh) -> dict:
    """Stacked per-shard hint encodings: (rules, batch, ...) sharded."""
    ba = batch_axes(mesh)
    specs = {k: P("rules", ba, *([None] * (v.ndim - 2)))
             for k, v in q.items()}
    return put_many(mesh, specs, q)


def _donate_queries(mesh: Mesh, argnums: tuple) -> dict:
    """jit kwargs donating the per-dispatch QUERY buffers (tables are
    reused across dispatches and must never be donated). Donation lets
    XLA alias the uploaded probe arrays instead of copying them —
    real-accelerator meshes only: the XLA CPU runtime ignores donation
    with a per-compile warning, which is noise on the virtual test
    mesh."""
    devs = mesh.devices.reshape(-1)
    if len(devs) and devs[0].platform != "cpu":
        return {"donate_argnums": argnums}
    return {}


def make_sharded_hint_fn(mesh: Mesh, table_keys_ndim: dict,
                         query_keys_ndim: dict, kernel=None):
    """-> jitted fn(stacked_table, stacked_queries, shard_size) -> [B] i32
    global hint-rule index (-1 none) for the ENGINE's jax-sharded
    backends. `kernel` is the per-shard matcher — hashmatch (cuckoo,
    default) or fphash's hint_fp_match; both share the (idx, level)
    contract. shard_size is a traced scalar, so rule-count changes within
    the same caps reuse the compiled program; caps (shape) changes just
    retrace. Winner = pmax(match level) then pmin(global index) among
    level winners — Upstream.java:187 semantics as an ICI reduction."""
    import jax.numpy as jnp

    from ..ops.hashmatch import hint_hash_match
    hint_match = kernel or hint_hash_match

    BIG = 2 ** 30

    def body(ht, hq, shard_size):
        sid = jax.lax.axis_index("rules").astype(jnp.int32)
        ht0 = {k: v[0] for k, v in ht.items()}
        hq0 = {k: v[0] for k, v in hq.items()}
        hidx, hlvl = hint_match(ht0, hq0)
        lvl = jnp.where(hidx >= 0, hlvl, 0)
        best_lvl = jax.lax.pmax(lvl, "rules")
        gidx = jnp.where((lvl == best_lvl) & (hidx >= 0),
                         sid * shard_size + hidx, BIG)
        gmin = jax.lax.pmin(gidx, "rules")
        return jnp.where(best_lvl > 0, gmin, -1)

    # ndim values are the STACKED ndims (leading shard axis included)
    ba = batch_axes(mesh)
    in_specs = (
        {k: P("rules", *([None] * (nd - 1)))
         for k, nd in table_keys_ndim.items()},
        {k: P("rules", ba, *([None] * (nd - 2)))
         for k, nd in query_keys_ndim.items()},
        P(),
    )
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(ba)),
                   **_donate_queries(mesh, (1,)))


def make_sharded_cidr_fn(mesh: Mesh, table_keys_ndim: dict,
                         with_port: bool, kernel=None):
    """-> jitted fn(stacked_table, a16, fam, [port,] shard_size) -> [B]
    i32 global first-match index (-1 none); first-match = one pmin over
    global indices (insert order is preserved across contiguous rule
    slices)."""
    import jax.numpy as jnp

    from ..ops.hashmatch import cidr_hash_match
    cidr_match = kernel or cidr_hash_match

    BIG = 2 ** 30

    if with_port:
        def body(t, a16, fam, port, shard_size):
            sid = jax.lax.axis_index("rules").astype(jnp.int32)
            t0 = {k: v[0] for k, v in t.items()}
            li = cidr_match(t0, a16, fam, port)
            g = jax.lax.pmin(jnp.where(li >= 0, sid * shard_size + li, BIG),
                             "rules")
            return jnp.where(g < BIG, g, -1)
        ba = batch_axes(mesh)
        q_specs = (P(ba, None), P(ba), P(ba), P())
    else:
        def body(t, a16, fam, shard_size):
            sid = jax.lax.axis_index("rules").astype(jnp.int32)
            t0 = {k: v[0] for k, v in t.items()}
            li = cidr_match(t0, a16, fam, None)
            g = jax.lax.pmin(jnp.where(li >= 0, sid * shard_size + li, BIG),
                             "rules")
            return jnp.where(g < BIG, g, -1)
        ba = batch_axes(mesh)
        q_specs = (P(ba, None), P(ba), P())

    in_specs = (
        {k: P("rules", *([None] * (nd - 1)))  # stacked ndims
         for k, nd in table_keys_ndim.items()},
    ) + q_specs
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(ba)),
                   **_donate_queries(mesh, (1, 2, 3) if with_port
                                     else (1, 2)))


def make_sharded_classify(mesh: Mesh, hint_stab, route_stab, acl_stab,
                          example_hq: dict):
    """-> jitted fn(ht, rt, at, hq, a16, fam, port) -> [B, 3] i32 global
    (hint idx, route idx, acl idx), -1 for no match; runs the full hash
    classify SPMD over the (batch, rules) mesh. example_hq: one output
    of encode_hint_queries_sharded (shapes fix the query specs)."""
    import jax.numpy as jnp

    from ..ops.hashmatch import cidr_hash_match, hint_hash_match

    BIG = 2 ** 30
    h_size = hint_stab.shard_size
    r_size = route_stab.shard_size
    a_size = acl_stab.shard_size

    def body(ht, rt, at, hq, a16, fam, port):
        sid = jax.lax.axis_index("rules").astype(jnp.int32)
        ht0 = {k: v[0] for k, v in ht.items()}
        hq0 = {k: v[0] for k, v in hq.items()}
        hidx, hlvl = hint_hash_match(ht0, hq0)
        lvl = jnp.where(hidx >= 0, hlvl, 0)
        best_lvl = jax.lax.pmax(lvl, "rules")
        gidx = jnp.where((lvl == best_lvl) & (hidx >= 0),
                         sid * h_size + hidx, BIG)
        gmin = jax.lax.pmin(gidx, "rules")
        h_global = jnp.where(best_lvl > 0, gmin, -1)

        def cidr_global(t, port_, size):
            t0 = {k: v[0] for k, v in t.items()}
            li = cidr_hash_match(t0, a16, fam, port_)
            g = jax.lax.pmin(jnp.where(li >= 0, sid * size + li, BIG),
                             "rules")
            return jnp.where(g < BIG, g, -1)

        r_global = cidr_global(rt, None, r_size)
        a_global = cidr_global(at, port, a_size)
        return jnp.stack([h_global, r_global, a_global], axis=1)

    ba = batch_axes(mesh)
    in_specs = (
        _leading_rules_spec(hint_stab.arrays),
        _leading_rules_spec(route_stab.arrays),
        _leading_rules_spec(acl_stab.arrays),
        {k: P("rules", ba, *([None] * (v.ndim - 2)))
         for k, v in example_hq.items()},
        P(ba, None), P(ba), P(ba),
    )
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(ba, None)))
