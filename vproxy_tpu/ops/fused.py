"""Fused classify+pick dispatch — one launch, one memory sweep per batch.

The unfused dispatch chain — FNV hash, cuckoo probe, hint gather,
verdict resolve, Maglev pick — rides several XLA dispatches per batch,
so every batch pays multiple launch overheads and multiple passes over
the tables. Pope et al.
(MLSys'23) is the template: fixed-shape batches amortize launch
overhead only when the per-batch work is ONE fused program, and Maglev
(Eisenbud, NSDI'16) makes the pick table just another gather that
belongs inside the same sweep.

Two layers live here:

* **Packing** (`pack_hint_table`): the compiled hint
  hash table (ops/hashmatch) re-packed into int8/int32 layouts chosen
  for a single linear sweep. The per-rule record — active flag, port,
  host/uri kind+len, uri score — becomes ONE int32 row (`pk_meta`,
  [r_cap, 8]) and the host+uri compare bytes ONE uint8 row
  (`pk_bytes`, [r_cap, hw+uw]), so resolving a candidate is two row
  gathers instead of the nine separate-array gathers the unfused
  kernel pays. The cuckoo slot side packs the same way: (used/klen,
  bucket_start, bucket_count) co-locate in one int32 row per slot
  (`pk_hslot`/`pk_uslot`), halving the probe gathers.
  Packing is pure vectorized numpy and runs INSIDE the matcher's
  standby compile (rules/engine.py), so packed generations publish
  through the same double-buffered TableInstaller swap as everything
  else.

* **The fused kernel** (`fused_classify_pick` / `fused_jit`): one
  jitted program taking the encoded query batch — as served, one
  arena holding its columns and the Maglev slots (hashmatch
  QueryArena) — plus the published snapshot's packed hint table and
  Maglev column and returning (verdict, pick) stacked [B, 2] — one
  XLA launch, one h2d argument, one d2h transfer per batch. Verdicts are bit-identical to
  `hashmatch.hint_hash_match` (same formulas, same i32 packing
  reduction; only the gather layout changed) and picks bit-identical
  to `maglev._device_take` (same host-side FNV slots, same clipped
  take). tests/test_fused.py proves both on randomized 100k-rule
  tables.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import cuckoo as CK
from .hashmatch import DOT, HOST_SHIFT, jit_packed, unpack_hint_arena


# ------------------------------------------------------------- packing

def pack_hint_table(a: dict) -> dict:
    """HashHintTable.arrays -> packed numpy arrays (see module doc).

    Column map (pk_meta, int32 [r_cap, 8]):
      0 active  1 port  2 host_kind  3 host_len
      4 uri_kind  5 uri_len  6 uri_score  7 reserved
    pk_bytes (uint8 [r_cap, hw+uw]): [0:hw] reversed host bytes,
    [hw:] uri bytes — hw is carried statically by pk_hsplit's shape.
    pk_hslot/pk_uslot (int32 [C, 4]): 0 klen-or--1-when-unused,
    1 bucket_start, 2 bucket_count, 3 reserved.

    Static specialization: a generation with ZERO uri rules (no
    normal, no wildcard) can never match by uri, so the uri half of
    the sweep — probe tables, uri byte columns, wildcard list — is
    OMITTED from the packed dict entirely. The dict's key set is part
    of the jit trace structure, so the compiled program for such a
    table simply has no uri work in it (lb-host10k's table is pure
    host rules; this is where its sweep bytes go)."""
    r_cap = a["r_active"].shape[0]
    hw = a["r_host"].shape[1]
    has_uri = bool((a["r_uri_kind"] > 0).any())
    meta = np.zeros((r_cap, 8), np.int32)
    meta[:, 0] = a["r_active"]
    meta[:, 1] = a["r_port"]
    meta[:, 2] = a["r_host_kind"]
    meta[:, 3] = a["r_host_len"]
    meta[:, 4] = a["r_uri_kind"]
    meta[:, 5] = a["r_uri_len"]
    meta[:, 6] = a["r_uri_score"]
    CK.coop_yield()  # standby-compile pacing: multi-MB memcpys below
    by = np.concatenate([a["r_host"], a["r_uri"]], axis=1) if has_uri \
        else np.ascontiguousarray(a["r_host"])
    CK.coop_yield()

    def slot_pack(used, klen, bs, bc):
        s = np.zeros((used.shape[0], 4), np.int32)
        s[:, 0] = np.where(used, klen, -1)
        s[:, 1] = bs
        s[:, 2] = bc
        return s

    out = {
        "pk_meta": meta, "pk_bytes": by,
        "pk_hsplit": np.zeros(hw, np.int8),  # hw as a static shape
        "pk_hslot": slot_pack(a["hk_used"], a["hk_len"], a["hk_bs"],
                              a["hk_bc"]),
        "pk_hkey": a["hk_bytes"],
        "hb_items": a["hb_items"], "wh_idx": a["wh_idx"],
        "bh_iota": a["bh_iota"],
    }
    if has_uri:
        out.update({
            "pk_uslot": slot_pack(a["uk_used"], a["uk_len"], a["uk_bs"],
                                  a["uk_bc"]),
            "pk_ukey": a["uk_bytes"], "ub_items": a["ub_items"],
            "wu_idx": a["wu_idx"], "bu_iota": a["bu_iota"],
        })
    CK.coop_yield()
    return out


# ------------------------------------------------------- fused kernel

def _packed_probe(slots, plen, pslot, kbytes, qbytes, iota):
    """Byte-verified cuckoo probe against the PACKED slot rows: one
    [B, P, 4] gather answers used+klen+bucket in a single sweep (the
    unfused kernel pays four). Same candidate set as
    hashmatch._probe_buckets: unused slots carry klen -1, and a valid
    probe's plen is >= 0, so (klen == plen) subsumes the used test."""
    k = kbytes.shape[1]
    s = jnp.maximum(slots, 0)
    srec = pslot[s]  # [B, P, 4] — the ONE slot gather
    ok = (slots >= 0) & (srec[..., 0] == plen)
    kb = kbytes[s]  # [B, P, K]
    span = jnp.arange(k, dtype=jnp.int32)
    eq = (kb == qbytes[:, None, :k]) | (span[None, None, :] >= plen[:, :, None])
    ok = ok & jnp.all(eq, axis=-1)
    start, cnt = srec[..., 1], srec[..., 2]
    j = iota[None, None, :]
    return jnp.where(ok[:, :, None] & (j < cnt[:, :, None]),
                     start[:, :, None] + j, -1)


def _hint_verdict_packed(t: dict, q: dict):
    """hint_hash_match over the packed layout: candidate resolve is
    TWO row gathers (pk_meta + pk_bytes) instead of nine array
    gathers. Formula-for-formula the unfused kernel — bit-identical
    winners (tests/test_fused.py parity)."""
    r_cap = t["pk_meta"].shape[0]
    b = q["hostb"].shape[0]
    hw = t["pk_hsplit"].shape[0]
    has_uri = "pk_uslot" in t  # static: uri-free tables compile a
    #                            program with NO uri work (pack doc)

    # stage names as in hashmatch.hint_hash_match (jax.named_scope:
    # metadata only, the compiled program is the same)
    with jax.named_scope("hint_probe"):
        ch1 = _packed_probe(q["hp_slot1"], q["hp_len"], t["pk_hslot"],
                            t["pk_hkey"], q["hostb"], t["bh_iota"])
        ch2 = _packed_probe(q["hp_slot2"], q["hp_len"], t["pk_hslot"],
                            t["pk_hkey"], q["hostb"], t["bh_iota"])
    with jax.named_scope("hint_candidates"):
        host_cand = jnp.where(ch1 >= 0,
                              t["hb_items"][jnp.maximum(ch1, 0)], -1)
        host_cand2 = jnp.where(ch2 >= 0,
                               t["hb_items"][jnp.maximum(ch2, 0)], -1)
        parts = [host_cand.reshape(b, -1), host_cand2.reshape(b, -1)]
    if has_uri:
        with jax.named_scope("hint_probe"):
            cu1 = _packed_probe(q["up_slot1"], q["up_len"], t["pk_uslot"],
                                t["pk_ukey"], q["urib"], t["bu_iota"])
            cu2 = _packed_probe(q["up_slot2"], q["up_len"], t["pk_uslot"],
                                t["pk_ukey"], q["urib"], t["bu_iota"])
        with jax.named_scope("hint_candidates"):
            parts.append(jnp.where(
                cu1 >= 0, t["ub_items"][jnp.maximum(cu1, 0)], -1)
                .reshape(b, -1))
            parts.append(jnp.where(
                cu2 >= 0, t["ub_items"][jnp.maximum(cu2, 0)], -1)
                .reshape(b, -1))
    with jax.named_scope("hint_candidates"):
        parts.append(jnp.broadcast_to(t["wh_idx"][None],
                                      (b, t["wh_idx"].shape[0])))
        if has_uri:
            parts.append(jnp.broadcast_to(t["wu_idx"][None],
                                          (b, t["wu_idx"].shape[0])))
        cand = jnp.concatenate(parts, axis=1)  # [B, NC]

    with jax.named_scope("hint_score"):
        c = jnp.maximum(cand, 0)
        meta = t["pk_meta"][c]   # [B, NC, 8] — one sweep over the records
        by = t["pk_bytes"][c]    # [B, NC, hw+uw] — one sweep over the bytes
        valid = (cand >= 0) & (meta[..., 0] > 0)

        rp = meta[..., 1]
        pg = (q["port"][:, None] == 0) | (rp == 0) | \
            (q["port"][:, None] == rp)

        hk, hl_ = meta[..., 2], meta[..., 3]
        rb = by[..., :hw]
        span = jnp.arange(hw, dtype=jnp.int32)
        heq = jnp.all((rb == q["hostb"][:, None, :hw]) |
                      (span[None, None, :] >= hl_[:, :, None]), axis=-1)
        exact = heq & (hl_ == q["hlen"][:, None])
        boundary = jnp.take_along_axis(
            q["hostb"], jnp.clip(hl_, 0, hw - 1), axis=1)
        suffix = heq & (hl_ < q["hlen"][:, None]) & (boundary == DOT)
        host_level = jnp.maximum(
            jnp.maximum(jnp.where(exact, 3, 0), jnp.where(suffix, 2, 0)),
            jnp.where(hk == 2, 1, 0))
        host_level = jnp.where((hk > 0) & q["has_host"][:, None],
                               host_level, 0)

        if has_uri:
            uw = by.shape[-1] - hw
            uk, ul = meta[..., 4], meta[..., 5]
            ub = by[..., hw:]
            uspan = jnp.arange(uw, dtype=jnp.int32)
            ueq = jnp.all((ub == q["urib"][:, None, :uw]) |
                          (uspan[None, None, :] >= ul[:, :, None]), axis=-1)
            prefix = ueq & (ul <= q["ulen"][:, None])
            uri_level = jnp.maximum(jnp.where(prefix, meta[..., 6], 0),
                                    jnp.where(uk == 2, 1, 0))
            uri_level = jnp.where((uk > 0) & q["has_uri"][:, None],
                                  uri_level, 0)
        else:
            uri_level = 0  # no uri rules exist: nothing can score by uri

        level = (host_level << HOST_SHIFT) + uri_level
        level = jnp.where(valid & pg, level, 0)
    from .hashmatch import _reduce_best
    with jax.named_scope("hint_reduce"):
        return _reduce_best(level, c, r_cap)


def fused_classify_pick(ht: dict, q: dict, mtab, slots):
    """THE fused program: hint verdict + Maglev pick in one compiled
    launch. -> int32 [B, 2] (verdict, pick). `slots` are host-side FNV
    Maglev slots (the shared hash contract of rules/maglev.py) so the
    pick column is bit-identical with every other pick plane."""
    v, _level = _hint_verdict_packed(ht, q)
    with jax.named_scope("maglev_pick"):
        p = jnp.take(mtab, slots, mode="clip").astype(jnp.int32)
    return jnp.stack([v, p], axis=1)


def _fused_packed(ht: dict, mtab, buf, layout):
    q, slots = unpack_hint_arena(buf, layout)
    return fused_classify_pick(ht, q, mtab, slots)


# the served entry: (packed hint table, Maglev table, arena, layout)
fused_jit = jit_packed("fused_classify_pick", _fused_packed)


def fused_group_pick(ht: dict, q: dict, rule_group, owner, set_tab, slots):
    """The grouped fused program: hint verdict, then the pick from the
    Maglev table of the server-group the matched rule names — a
    dependent chain (verdict -> rule_group row -> that group's table)
    inside one compiled launch. -> int32 [B, 2] (verdict, pick).

    rule_group (int32 [r_cap, 2], published with the hint generation):
    the set row the rule's group owns and the token that row was handed
    out under, (-1, -1) for a rule that names no group. owner (int32
    [groups_cap], published with the set generation): the token under
    which each row's table was installed, -1 where the row holds none.
    A row answers only under its own token, so a hint generation paired
    with a set generation in which the row belongs to nobody yet, or to
    another group already, answers pick -1 — never another group's
    backend. set_tab ([groups_cap, M], rules/maglev.MaglevTableSet): one
    table a row; `slots` are the host-side FNV slots of the one M
    (maglev.flow_slots, the shared hash contract)."""
    v, _level = _hint_verdict_packed(ht, q)
    with jax.named_scope("group_pick"):
        rg = rule_group[jnp.maximum(v, 0)]          # [B, 2]
        row = jnp.maximum(rg[:, 0], 0)
        ok = (v >= 0) & (rg[:, 0] >= 0) & (owner[row] == rg[:, 1])
        p = set_tab[row, jnp.clip(slots, 0, set_tab.shape[1] - 1)]
        p = jnp.where(ok, p.astype(jnp.int32), -1)
    return jnp.stack([v, p], axis=1)


def _group_packed(ht: dict, rule_group, owner, set_tab, buf, layout):
    q, slots = unpack_hint_arena(buf, layout)
    return fused_group_pick(ht, q, rule_group, owner, set_tab, slots)


# the served entry: (packed hint table, rule -> group column, owner
# tokens, pick-table set, arena, layout)
group_jit = jit_packed("fused_group_pick", _group_packed)
