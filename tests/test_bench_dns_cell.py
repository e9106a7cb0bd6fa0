"""Tier-1 copy of benchmark/tests/test_dns_cell.py: the DNS cell of the
benchmark (`dns-100k.udp-w256`) at toy size through run.py's own path —
its control and each planted fault turn `correct` false; and the traced
line carries the drain's hold (`dns_lookups_per_flush`) and the
service's hand-over to the server's loop (`loop_callbacks_per_handoff`)."""
import importlib.util
import os

import pytest

_path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "tests", "test_dns_cell.py")
_spec = importlib.util.spec_from_file_location("bench_test_dns_cell", _path)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

test_toy_cell_is_correct_through_its_own_checks = \
    _mod.test_toy_cell_is_correct_through_its_own_checks
test_control_is_not_correct = _mod.test_control_is_not_correct
test_a_dropped_response_reads_as_undelivered = \
    _mod.test_a_dropped_response_reads_as_undelivered
test_a_lookup_the_host_answers_trips_the_device_checks = \
    _mod.test_a_lookup_the_host_answers_trips_the_device_checks
test_an_address_no_group_owns_is_a_wrong_answer = \
    _mod.test_an_address_no_group_owns_is_a_wrong_answer


@pytest.fixture(scope="module")
def traced():
    """One traced toy run, read by the two cases below."""
    return _mod.toy(45, trace=True)


def test_traced_line_reads_the_drains_hold(traced):
    """`dns_lookups_per_flush` (the `dns/flush` span) is in the traced
    line, a flush handing over at least one lookup."""
    m = traced["metrics"]
    assert traced["correct"] and m["dns_lookups_per_flush"]["value"] >= 1


def test_traced_line_reads_the_loop_handoff(traced):
    """`loop_callbacks_per_handoff` (the `engine/handoff` span) is in the
    traced line: the service hands the server's loop its callbacks, at
    least one a hand-over."""
    m = traced["metrics"]
    assert traced["correct"] \
        and m["loop_callbacks_per_handoff"]["value"] >= 1
