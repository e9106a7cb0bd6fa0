"""Bytes one lookup needs — the roofline's numerator.

Counted from the query and the rule semantics, never from the layout
that happens to serve them, so a later kernel or layout change moves
the share of the roofline and not the count. Per query: the key bytes
uploaded, one 64-byte row for every candidate key that the first-match
rule obliges a lookup to test, and the 4-byte verdict read back. The
lookups are byte compares and gathers on the vector unit, for which
the chip publishes no peak, so the roofline is the memory one alone
(peaks.json `hbm_bytes_per_s`); the matrix unit's int8 peak is not a
bound any of these kernels could approach, and is not used.
"""
from __future__ import annotations

ROW = 64        # one table row: a key and its rule record
VERDICT = 4


def hint_bytes(q: tuple, rule_uri_lengths: frozenset) -> int:
    """Candidate keys of a hint: every host the rules could name (the
    host itself, each dot-suffix, "*") x every uri they could name (no
    uri, "*", each prefix of a length some rule has) x the port being
    named or left open."""
    host, _port, uri = q
    hosts = 2 + host.count(".") if host else 1
    uris = 1
    if uri is not None:
        uris += 1 + sum(1 for n in rule_uri_lengths if n <= len(uri))
    rows = hosts * uris * 2
    key = len(host or "") + len(uri or "") + 2
    return key + rows * ROW + VERDICT


def cidr_bytes(nets: list, acl: bool) -> int:
    """One candidate per prefix length the table holds; an ACL row
    carries its port range in the same row. Key: 16 address bytes
    (+ 2 of port)."""
    rows = len({n[1] for n in nets})
    return 16 + (2 if acl else 0) + rows * ROW + VERDICT
