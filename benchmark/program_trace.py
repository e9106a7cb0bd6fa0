"""The second place the benchmark touches the program, and a read-only
one: the totals the program keeps of its own spans while its tracing is
on (`vproxy_tpu.utils.trace.span_totals()`; `program.Instrument` turns
tracing on before the ramp of a traced run and restores it after the
drain, so the totals cover ramp + window + drain and nothing of
set-up). A program without such totals gives an empty dict: the readers
then find nothing and their metrics are left out of the line.
"""
from __future__ import annotations


def span_totals() -> dict:
    """{"plane/span": {"n", "sum_ns", "sum_cpu_ns", "sum_items",
    "buckets", "first_ns", "last_ns"}}"""
    from vproxy_tpu.utils import trace
    read = getattr(trace, "span_totals", None)
    return read() if read is not None else {}

