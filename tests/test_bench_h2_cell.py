"""Tier-1 copy of benchmark/tests/test_h2_cell.py: the h2 cell of the
benchmark (`h2lb-groups256.h2-c16m48`) at toy size through run.py's own
path — correct with its own checks and traced metrics, its control and
each planted fault turn `correct` false; the traced line carries the
service's hand-over to the h2 loop (`loop_callbacks_per_handoff`) and
the loop's work on each answer (`h2_selected_us_per_stream`)."""
import importlib.util
import os

_path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "tests", "test_h2_cell.py")
_spec = importlib.util.spec_from_file_location("bench_test_h2_cell", _path)
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)

test_toy_cell_is_correct_through_its_own_checks = \
    _mod.test_toy_cell_is_correct_through_its_own_checks
test_control_is_not_correct = _mod.test_control_is_not_correct
test_a_wrong_group_is_a_wrong_answer = _mod.test_a_wrong_group_is_a_wrong_answer
test_a_dropped_select_reads_as_undelivered = \
    _mod.test_a_dropped_select_reads_as_undelivered
test_a_reset_stream_trips_its_check = _mod.test_a_reset_stream_trips_its_check
test_a_missing_forwarded_for_trips_its_check = \
    _mod.test_a_missing_forwarded_for_trips_its_check
test_a_select_the_host_answers_trips_the_device_checks = \
    _mod.test_a_select_the_host_answers_trips_the_device_checks


def test_traced_line_reads_the_loop_handoff():
    """`loop_callbacks_per_handoff` (the `engine/handoff` span) is in the
    traced line: the service hands the h2 loop its streams' answers, at
    least one a hand-over; and so is `h2_selected_us_per_stream` (the
    `l7/selected` span), the loop's work on each answer."""
    t = _mod.toy(2**31 + 61, trace=True)
    m = t["metrics"]
    assert t["correct"] and m["loop_callbacks_per_handoff"]["value"] >= 1
    assert m["h2_selected_us_per_stream"]["value"] > 0
