"""Batched JAX matchers over compiled tables.

These are the device kernels behind classify(): jit once, then feed
micro-batches. Selection semantics reproduce the reference exactly:

* hint match: strictly-greater max level, earliest rule wins ties
  (Upstream.searchForGroup, Upstream.java:187-198); level encoding is
  (host_level << 10) + uri_level (Hint.java:92-160).
* cidr first-match: smallest rule index among matching patterns
  (RouteTable.lookup RouteTable.java:44; SecurityGroup.allow
  SecurityGroup.java:38-43).

All matchers return plain arrays so they compose under jit/pjit and can
be sharded over a device mesh along the rule axis (see parallel/mesh.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .bitmatch import mismatch_counts, unpack_bits
from .tables import MATCH_CHUNK

HOST_SHIFT = 10
# Plain int, NOT jnp.int32(-1): a module-level jnp constant would touch
# the device backend at import time — and importing must never claim
# the chip (one process per chip; a supervisor imports this package).
# It weak-types to i32 inside the jitted matchers either way.
NO_MATCH = -1


def hint_match(table: dict, q_host: jnp.ndarray, q_has_host: jnp.ndarray,
               q_uri_bits: jnp.ndarray,
               q_has_uri: jnp.ndarray, q_port: jnp.ndarray):
    # NOTE: uri scoring only needs the RULE-side length (uri_score): an exact
    # uri match scores len(hint.uri)+1 and a prefix match len(rule.uri)+1,
    # which coincide whenever both fire (Hint.java:144-152).
    """-> (best_idx [B] i32 (-1 none), best_level [B] i32).

    q_host: [B, HOST_SLOT] uint8 (reversed bytes + length byte)
    q_uri_bits: [B, MAX_URI*8] f32 bit-planes
    """
    cap = table["active"].shape[0]
    hb = unpack_bits(q_host)  # [B, HOST_SLOT*8]
    level = _hint_levels(table, hb, q_has_host, q_uri_bits, q_has_uri, q_port)
    # strictly-greater max, earliest index wins ties
    order = jnp.arange(cap, dtype=jnp.int32)
    key = level * cap + (cap - 1 - order)[None]
    idx = jnp.argmax(key, axis=1).astype(jnp.int32)
    best_level = jnp.take_along_axis(level, idx[:, None], axis=1)[:, 0]
    return jnp.where(best_level > 0, idx, NO_MATCH), best_level


def cidr_first_match(table: dict, q_addr: jnp.ndarray, q_family: jnp.ndarray,
                     q_port: jnp.ndarray | None = None):
    """-> first-matching rule index [B] i32, or -1.

    q_addr: [B, 16] uint8 canonical; q_family: [B] i32 (0=v4, 1=v6).
    q_port: [B] i32 for ACL tables (port-range gate), None for routes.
    """
    cap3 = table["valid"].shape[0]
    cap = cap3 // 3
    ab = unpack_bits(q_addr)  # [B, 128]
    mm = mismatch_counts(ab, table["w"], table["c"])  # [B, cap*3]
    match = (mm == 0) & table["valid"][None] & (
        q_family[:, None] == table["family"][None])
    rule_idx = (jnp.arange(cap3, dtype=jnp.int32) // 3)[None]  # pattern -> rule
    if q_port is not None:
        port_ok = (table["min_port"][None, rule_idx[0]] <= q_port[:, None]) & (
            q_port[:, None] <= table["max_port"][None, rule_idx[0]])
        match = match & port_ok
    masked = jnp.where(match, rule_idx, jnp.int32(cap))
    first = jnp.min(masked, axis=1).astype(jnp.int32)
    return jnp.where(first < cap, first, NO_MATCH)


def _lex_better(lvl, idx, best_lvl, best_idx):
    """(level, earliest-index) lexicographic winner — avoids the level*cap
    int32 key overflow for very large tables."""
    take = (lvl > best_lvl) | ((lvl == best_lvl) & (idx < best_idx))
    return jnp.where(take, lvl, best_lvl), jnp.where(take, idx, best_idx)


def hint_match_chunked(table: dict, q_host: jnp.ndarray, q_has_host: jnp.ndarray,
                       q_uri_bits: jnp.ndarray, q_has_uri: jnp.ndarray,
                       q_port: jnp.ndarray, chunk: int = MATCH_CHUNK):
    """hint_match for big tables: lax.scan over rule chunks so the [B, cap]
    mismatch matrix never materializes beyond [B, chunk]."""
    cap = table["active"].shape[0]
    if cap <= chunk:
        return hint_match(table, q_host, q_has_host, q_uri_bits, q_has_uri, q_port)
    assert cap % chunk == 0, (cap, chunk)
    n_chunks = cap // chunk
    b = q_host.shape[0]
    hb = unpack_bits(q_host)

    def slice_chunk(i):
        s2 = i * chunk * 2
        s1 = i * chunk
        return {
            "host_w": jax.lax.dynamic_slice_in_dim(table["host_w"], s2, chunk * 2, 1),
            "host_c": jax.lax.dynamic_slice_in_dim(table["host_c"], s2, chunk * 2, 0),
            "host_valid": jax.lax.dynamic_slice_in_dim(table["host_valid"], s1, chunk, 0),
            "host_wild": jax.lax.dynamic_slice_in_dim(table["host_wild"], s1, chunk, 0),
            "uri_w": jax.lax.dynamic_slice_in_dim(table["uri_w"], s1, chunk, 1),
            "uri_c": jax.lax.dynamic_slice_in_dim(table["uri_c"], s1, chunk, 0),
            "uri_valid": jax.lax.dynamic_slice_in_dim(table["uri_valid"], s1, chunk, 0),
            "uri_wild": jax.lax.dynamic_slice_in_dim(table["uri_wild"], s1, chunk, 0),
            "uri_score": jax.lax.dynamic_slice_in_dim(table["uri_score"], s1, chunk, 0),
            "port": jax.lax.dynamic_slice_in_dim(table["port"], s1, chunk, 0),
            "active": jax.lax.dynamic_slice_in_dim(table["active"], s1, chunk, 0),
        }

    def step(carry, i):
        best_lvl, best_idx = carry
        sub = slice_chunk(i)
        level = _hint_levels(sub, hb, q_has_host, q_uri_bits, q_has_uri, q_port)
        order = jnp.arange(chunk, dtype=jnp.int32)
        key = level * chunk + (chunk - 1 - order)[None]
        loc = jnp.argmax(key, axis=1).astype(jnp.int32)
        lvl = jnp.take_along_axis(level, loc[:, None], axis=1)[:, 0]
        idx = loc + i * chunk
        return _lex_better(lvl, idx, best_lvl, best_idx), None

    init = (jnp.zeros(b, jnp.int32), jnp.full(b, 2**31 - 1, jnp.int32))
    (best_lvl, best_idx), _ = jax.lax.scan(
        step, init, jnp.arange(n_chunks, dtype=jnp.int32))
    return jnp.where(best_lvl > 0, best_idx, NO_MATCH), best_lvl


def cidr_first_match_chunked(table: dict, q_addr: jnp.ndarray,
                             q_family: jnp.ndarray,
                             q_port: jnp.ndarray | None = None,
                             chunk: int = MATCH_CHUNK):
    """cidr_first_match scanned over rule chunks (chunk counts rules, each
    rule has 3 pattern slots)."""
    cap3 = table["valid"].shape[0]
    cap = cap3 // 3
    if cap <= chunk:
        return cidr_first_match(table, q_addr, q_family, q_port)
    assert cap % chunk == 0, (cap, chunk)
    n_chunks = cap // chunk
    b = q_addr.shape[0]
    ab = unpack_bits(q_addr)

    def step(carry, i):
        s3 = i * chunk * 3
        s1 = i * chunk
        sub = {
            "w": jax.lax.dynamic_slice_in_dim(table["w"], s3, chunk * 3, 1),
            "c": jax.lax.dynamic_slice_in_dim(table["c"], s3, chunk * 3, 0),
            "family": jax.lax.dynamic_slice_in_dim(table["family"], s3, chunk * 3, 0),
            "valid": jax.lax.dynamic_slice_in_dim(table["valid"], s3, chunk * 3, 0),
        }
        mm = mismatch_counts(ab, sub["w"], sub["c"])
        match = (mm == 0) & sub["valid"][None] & (
            q_family[:, None] == sub["family"][None])
        rule_idx = (jnp.arange(chunk * 3, dtype=jnp.int32) // 3)[None]
        if q_port is not None:
            minp = jax.lax.dynamic_slice_in_dim(table["min_port"], s1, chunk, 0)
            maxp = jax.lax.dynamic_slice_in_dim(table["max_port"], s1, chunk, 0)
            port_ok = (minp[rule_idx[0]][None] <= q_port[:, None]) & (
                q_port[:, None] <= maxp[rule_idx[0]][None])
            match = match & port_ok
        masked = jnp.where(match, rule_idx + i * chunk, jnp.int32(cap))
        first = jnp.min(masked, axis=1).astype(jnp.int32)
        return jnp.minimum(carry, first), None

    init = jnp.full(b, cap, jnp.int32)
    first, _ = jax.lax.scan(step, init, jnp.arange(n_chunks, dtype=jnp.int32))
    return jnp.where(first < cap, first, NO_MATCH)


def _hint_levels(table, hb, q_has_host, q_uri_bits, q_has_uri, q_port):
    """[B, cap] match levels for one (sub-)table. Shared by direct/chunked."""
    cap = table["active"].shape[0]
    hmm = mismatch_counts(hb, table["host_w"], table["host_c"])
    hmatch = (hmm == 0).reshape(-1, cap, 2) & table["host_valid"][None]
    exact, suffix = hmatch[..., 0], hmatch[..., 1]
    host_level = jnp.maximum(
        jnp.maximum(exact * 3, suffix * 2),
        table["host_wild"][None].astype(jnp.int32) * 1,
    )
    host_level = jnp.where(q_has_host[:, None], host_level, 0)
    umm = mismatch_counts(q_uri_bits, table["uri_w"], table["uri_c"])
    prefix = (umm == 0) & table["uri_valid"][None]
    uri_level = jnp.maximum(
        prefix * table["uri_score"][None],
        table["uri_wild"][None].astype(jnp.int32) * 1,
    )
    uri_level = jnp.where(q_has_uri[:, None], uri_level, 0)
    level = (host_level << HOST_SHIFT) + uri_level
    port_ok = (q_port[:, None] == 0) | (table["port"][None] == 0) | (
        q_port[:, None] == table["port"][None])
    return jnp.where(port_ok & table["active"][None], level, 0)


@partial(jax.jit, static_argnames=())
def classify_all(hint_table: dict, route_table: dict, acl_table: dict,
                 hint_q: dict, route_q: dict, acl_q: dict):
    """The fused flagship step: one dispatch classifies a micro-batch of
    LB hints + DNS qnames (hint_q), route lookups and ACL checks."""
    h_idx, h_level = hint_match_chunked(
        hint_table, hint_q["host"], hint_q["has_host"],
        unpack_bits(hint_q["uri"]), hint_q["has_uri"], hint_q["port"])
    r_idx = cidr_first_match_chunked(route_table, route_q["addr"],
                                     route_q["family"])
    a_idx = cidr_first_match_chunked(acl_table, acl_q["addr"],
                                     acl_q["family"], acl_q["port"])
    a_allow = jnp.where(
        a_idx >= 0, acl_table["allow"][jnp.maximum(a_idx, 0)], False)
    return h_idx, h_level, r_idx, a_idx, a_allow


# jitted entry points for the engine: cache key = table shapes/dtypes, so
# same-capacity rule updates reuse the compiled program (no retrace)
hint_match_jit = jax.jit(hint_match_chunked, static_argnames=("chunk",))
cidr_match_jit = jax.jit(cidr_first_match_chunked, static_argnames=("chunk",))


def table_arrays(t) -> dict:
    """HintTable/CidrTable dataclass -> dict of arrays (jit-friendly pytree)."""
    import numpy as np
    out = {}
    for k, v in vars(t).items():
        if isinstance(v, np.ndarray):
            out[k] = v
    return out
