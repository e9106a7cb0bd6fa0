"""Pallas tier of the fused classify+pick contract — NOT a serving tier.

Same contract as `ops/fused.py`'s jitted program (packed tables in,
(verdict, pick) out, one launch), written as a scalar kernel: grid over
the batch, one query row per step, the packed tables left in `pl.ANY`
and read one row per candidate.

Status (TPU v5 lite, jax 0.9.0, PR 21): Mosaic refuses the kernel at
lowering, first on the `(1, w)` / `(1, 1)` query blocks ("the last two
dimensions of your block shape are divisible by 8 and 128 respectively,
or be equal to the respective dimensions of the overall array"). The
body behind that check does not lower either — each construct tried
alone in a micro-kernel with legal blocks: `pk_meta[ci, :]` on a
`pl.ANY` ref: "Loads are only allowed on VMEM and SMEM references. ANY
memory space can only be accessed using async_copy"; the uint8 row
compare + `jnp.all`: "Mosaic failed to compile TPU kernel: Invalid
relayout ... 'vector<32xi1>'"; `qhost[clip(hl)]`: "Unimplemented
primitive in Pallas TPU lowering: dynamic_slice" (scalar reads like
`hlen[0, 0]` from VMEM do compile). That is a rewrite (per-candidate
DMAs, i32 lanes, SMEM copies of the query row), not a repair, so the
engine serves the fused jit tier by name (`fused.kernel_mode()`
defaults to "jit"). This kernel runs only under an explicit
`VPROXY_TPU_FUSED_KERNEL=pallas`, which raises at the first dispatch
wherever it cannot compile, and in interpret mode
(`VPROXY_TPU_PALLAS_INTERPRET=1`) as the bit-verified statement of the
contract (tests/test_fused.py). Whether a Mosaic kernel is worth
writing is ROADMAP D3's question.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .hashmatch import DOT, HOST_SHIFT


def interpret_forced() -> bool:
    """VPROXY_TPU_PALLAS_INTERPRET=1: run the kernel in the Pallas
    interpreter (CPU-valid, slow) — the bit-verification lane for
    environments without a real accelerator."""
    return os.environ.get("VPROXY_TPU_PALLAS_INTERPRET", "0") == "1"


def _iota(n: int):
    # TPU wants >=2D iota; broadcasted_iota keeps the kernel Mosaic-
    # compatible while interpret mode doesn't care
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)[0]


def _fused_kernel(hostb, hlen, has_host, urib, ulen, has_uri, port,
                  hp_len, hp_s1, hp_s2, up_len, up_s1, up_s2, slots,
                  pk_meta, pk_bytes, pk_hslot, pk_hkey, pk_uslot,
                  pk_ukey, hb_items, ub_items, wh_idx, wu_idx, mtab,
                  out, *, hw: int, r_cap: int, bh: int, bu: int,
                  uri_rules: bool):
    """One query row per grid step: fold every candidate's packed
    score into the (max level, min index) reduction, then gather the
    Maglev pick — all inside one launch."""
    qhost = hostb[0, :]          # (hw,) VMEM-resident query windows
    quri = urib[0, :]
    qhlen = hlen[0, 0]
    qulen = ulen[0, 0]
    qport = port[0, 0]
    qhas_host = has_host[0, 0] > 0
    qhas_uri = has_uri[0, 0] > 0
    uw = quri.shape[0]
    hspan = _iota(hw)
    uspan = _iota(uw)

    def score(c):
        """Packed-record resolve: ONE meta row + ONE byte row per
        candidate (the layout's whole point); formulas bit-identical
        to fused._hint_verdict_packed. -> (level, index) for the
        running (max level, min index) fold — a pair carry instead of
        the i32 packing so the kernel is exact at ANY r_cap (the
        million-rule single table is the fused path's scale tier)."""
        ci = jnp.maximum(c, 0)
        meta = pk_meta[ci, :]    # (8,)
        byr = pk_bytes[ci, :]    # (hw+uw,)
        rp, hk, hl = meta[1], meta[2], meta[3]
        uk, ul, uscore = meta[4], meta[5], meta[6]
        pg = (qport == 0) | (rp == 0) | (qport == rp)
        heq = jnp.all((byr[:hw] == qhost) | (hspan >= hl))
        exact = heq & (hl == qhlen)
        boundary = qhost[jnp.clip(hl, 0, hw - 1)]
        suffix = heq & (hl < qhlen) & (boundary == DOT)
        host_level = jnp.maximum(
            jnp.maximum(jnp.where(exact, 3, 0), jnp.where(suffix, 2, 0)),
            jnp.where(hk == 2, 1, 0))
        host_level = jnp.where((hk > 0) & qhas_host, host_level, 0)
        if uri_rules:
            ueq = jnp.all((byr[hw:] == quri) | (uspan >= ul))
            prefix = ueq & (ul <= qulen)
            uri_level = jnp.maximum(jnp.where(prefix, uscore, 0),
                                    jnp.where(uk == 2, 1, 0))
            uri_level = jnp.where((uk > 0) & qhas_uri, uri_level, 0)
        else:  # uri-free table: nothing can score by uri (fused.py)
            uri_level = 0
        level = (host_level << HOST_SHIFT) + uri_level
        level = jnp.where((c >= 0) & (meta[0] > 0) & pg, level, 0)
        return level, ci

    def fold(best, c):
        """best = (best_level, best_idx): strictly-greater level wins;
        equal level keeps the SMALLEST index (Upstream.java:187's
        earliest-index tie rule, same winner as _reduce_best)."""
        lvl, ci = score(c)
        bl, bi = best
        better = (lvl > bl) | ((lvl == bl) & (lvl > 0) & (ci < bi))
        return (jnp.where(better, lvl, bl), jnp.where(better, ci, bi))

    def probe_fold(best, maxp, bcap, slot_row, len_row, pslot, pkey,
                   items, qb):
        """Fold all candidates of one probe family (maxp probes x bcap
        bucket slots); same candidate set as fused._packed_probe."""
        k = pkey.shape[1]
        kspan = _iota(k)

        def per_probe(p, best):
            slot = slot_row[0, p]
            plen = len_row[0, p]
            s = jnp.maximum(slot, 0)
            srec = pslot[s, :]
            kb = pkey[s, :]
            ok = (slot >= 0) & (srec[0] == plen) & \
                jnp.all((kb == qb[:k]) | (kspan >= plen))
            start, cnt = srec[1], srec[2]

            def per_bucket(j, best):
                take = ok & (j < cnt)
                c = jnp.where(take, items[jnp.where(take, start + j, 0)],
                              -1)
                return fold(best, c)

            return jax.lax.fori_loop(0, bcap, per_bucket, best)

        return jax.lax.fori_loop(0, maxp, per_probe, best)

    best = (jnp.int32(0), jnp.int32(r_cap))
    maxp = hp_s1.shape[1]
    lw = up_s1.shape[1]
    best = probe_fold(best, maxp, bh, hp_s1, hp_len, pk_hslot,
                      pk_hkey, hb_items, qhost)
    best = probe_fold(best, maxp, bh, hp_s2, hp_len, pk_hslot,
                      pk_hkey, hb_items, qhost)
    if uri_rules:
        best = probe_fold(best, lw, bu, up_s1, up_len, pk_uslot,
                          pk_ukey, ub_items, quri)
        best = probe_fold(best, lw, bu, up_s2, up_len, pk_uslot,
                          pk_ukey, ub_items, quri)

    def wild(j, best, items):
        return fold(best, items[j])

    best = jax.lax.fori_loop(
        0, wh_idx.shape[0], functools.partial(wild, items=wh_idx), best)
    if uri_rules:
        best = jax.lax.fori_loop(
            0, wu_idx.shape[0], functools.partial(wild, items=wu_idx),
            best)

    verdict = jnp.where(best[0] > 0, best[1], -1)
    pick = mtab[slots[0, 0]]
    out[0, 0] = verdict.astype(jnp.int32)
    out[0, 1] = pick.astype(jnp.int32)


def fused_classify_pick_pallas(ht: dict, q: dict, mtab, slots,
                               interpret: Optional[bool] = None):
    """The Pallas entry with the SAME contract as fused.fused_jit's
    (verdict, pick) form: packed hint table + encoded query batch +
    Maglev column/slots -> int32 [B, 2] in one pallas_call launch."""
    from jax.experimental import pallas as pl
    if interpret is None:
        interpret = interpret_forced()
    b, hw = q["hostb"].shape
    uw = q["urib"].shape[1]
    maxp = q["hp_slot1"].shape[1]
    lw = q["up_slot1"].shape[1]
    r_cap = int(ht["pk_meta"].shape[0])
    uri_rules = "pk_uslot" in ht  # uri-free layout (fused.py pack doc)
    if uri_rules:
        uslot, ukey = ht["pk_uslot"], ht["pk_ukey"]
        ub_items, wu_idx = ht["ub_items"], ht["wu_idx"]
        bu = int(ht["bu_iota"].shape[0])
    else:  # never-read dummies keep the ref count static
        uslot = np.zeros((1, 4), np.int32)
        ukey = np.zeros((1, 1), np.uint8)
        ub_items = np.full(1, -1, np.int32)
        wu_idx = np.full(1, -1, np.int32)
        bu = 1

    def col(a):  # (B,) scalars as (B, 1) i32 rows (2D-friendly blocks)
        return np.asarray(a).astype(np.int32).reshape(b, 1)

    row = lambda w: pl.BlockSpec((1, w), lambda i: (i, 0))
    one = pl.BlockSpec((1, 1), lambda i: (i, 0))
    # packed tables: whole-array refs, compiler-placed
    full = pl.BlockSpec(memory_space=pl.ANY)

    kernel = functools.partial(_fused_kernel, hw=hw, r_cap=r_cap,
                               bh=int(ht["bh_iota"].shape[0]),
                               bu=bu, uri_rules=uri_rules)
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            row(hw), one, one, row(uw), one, one, one,
            row(maxp), row(maxp), row(maxp),
            row(lw), row(lw), row(lw), one,
            full, full, full, full, full, full, full, full, full,
            full, full,
        ],
        out_specs=pl.BlockSpec((1, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 2), jnp.int32),
        interpret=interpret,
    )(q["hostb"], col(q["hlen"]), col(q["has_host"]), q["urib"],
      col(q["ulen"]), col(q["has_uri"]), col(q["port"]),
      q["hp_len"], q["hp_slot1"], q["hp_slot2"],
      q["up_len"], q["up_slot1"], q["up_slot2"],
      col(np.asarray(slots)),
      ht["pk_meta"], ht["pk_bytes"], ht["pk_hslot"], ht["pk_hkey"],
      uslot, ukey, ht["hb_items"], ub_items,
      ht["wh_idx"], wu_idx, mtab)
