"""The trace reduction on small synthetic traces (times in ns)."""
import pytest

from bench import trace as tr

OPS = [("fusion.1", 0, 10), ("gossip_reduce_w_resident_pallas.1", 10, 5),
       ("fusion.2", 12, 4), ("gossip_apply_w_resident_pallas.1", 30, 20),
       ("gossip_apply_w_resident_pallas.1", 60, 10)]


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 8), (0, 3), (3, 4), (7, 12)]) == [(0, 4), (5, 12)]


def test_busy_is_the_union_clipped_to_the_window():
    # busy [0, 16) + [30, 50) + [60, 70): clipped to [5, 65) -> 11 + 20 + 5
    assert tr.busy_ns(OPS, 5, 65) == 36
    assert tr.busy_ns(OPS, 0, 100) == 46


def test_idle_share_and_gaps():
    assert tr.gaps(OPS, 0, 100) == [(16, 30), (50, 60), (70, 100)]
    idle = 1 - tr.busy_ns(OPS, 0, 100) / 100
    assert idle == pytest.approx(0.54)
    assert tr.gaps([], 0, 10) == [(0, 10)]


def test_kernel_time_by_function_name():
    assert tr.time_by_name(OPS, "gossip_apply_w_resident_pallas") == (2, 30)
    assert tr.time_by_name(OPS, "gossip_reduce_w_resident_pallas") == (1, 5)
    assert tr.time_by_name(OPS, "no_such_kernel") == (0, 0)


@pytest.mark.parametrize("ops, exposed", [
    # hidden: the permute runs under a fusion from start to end
    ([("fusion.1", 0, 100), ("collective-permute-start.1", 10, 50)], 0),
    # partly exposed: 20 of its 50 ns run alone
    ([("fusion.1", 0, 40), ("collective-permute-done.1", 10, 50)], 20),
    # fully exposed, two of them, a fusion in between
    ([("collective-permute.1", 0, 10), ("fusion.1", 10, 5),
      ("collective-permute.2", 15, 10)], 20),
    # no permute at all
    ([("fusion.1", 0, 10)], 0),
])
def test_exposed_collective_time(ops, exposed):
    assert tr.exposed_ns(ops, tr.is_permute) == exposed


def test_breakdown_names_gaps_by_the_host_span_in_them():
    host = [("bench.next_wbatch", 16, 12), ("bench.step_fn", 52, 6)]
    gaps = tr.idle_gaps(OPS, host, 0, 80, n=10)
    assert gaps == [["bench.next_wbatch", 14e-9], ["bench.step_fn", 10e-9],
                    ["host", 10e-9]]
    top = tr.top_ops(OPS, n=2)
    assert top == [["gossip_apply_w_resident_pallas", 30e-9],
                   ["fusion", 14e-9]]


def test_containers_are_dropped_and_names_cut_to_the_instruction():
    ops = [("while.1", 0, 100), ("fusion.1", 0, 40), ("while.2", 50, 40),
           ("fusion.2", 50, 10), ("fusion.3", 70, 20), ("copy.1", 120, 5)]
    assert tr.leaves(ops) == [("fusion.1", 0, 40), ("fusion.2", 50, 10),
                              ("fusion.3", 70, 20), ("copy.1", 120, 5)]
    assert tr.op_name("%conditional = (s8[1,4]{1,0}) conditional(%x)") \
        == "conditional"
    assert tr.op_name("%gossip_apply_w_resident_pallas.1") \
        == "gossip_apply_w_resident_pallas.1"


def test_the_window_span_names_no_gap():
    host = [("bench.window", 0, 100)]
    assert tr.idle_gaps([("fusion.1", 0, 10)], host, 0, 20) == [
        ["host", 10e-9]]
