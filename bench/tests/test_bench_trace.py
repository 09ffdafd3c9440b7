"""The reduction of bench/trace.py on a small synthetic event list."""
import pytest
from bench_tiny import REPO  # noqa: F401  (puts the repository on sys.path)

from bench import trace as tr

S = tr.Span


def test_op_names_lose_the_hlo_text_and_suffix():
    assert tr.op_name("%conv_untiled.14 = s16[1,224,224,64]{3,2,1,0} custom-call(...)") == "conv_untiled"
    assert tr.op_name("%matmul_q16 = s16[8,4096] custom-call(...)") == "matmul_q16"
    assert tr.op_name("collective-permute-start.3") == "collective-permute-start"
    assert tr.op_name("fusion.12") == "fusion"


def test_overlapping_operations_count_once():
    ops = [S("a", 0, 10), S("b", 5, 15), S("c", 20, 30), S("d", 22, 25)]
    assert tr.busy_ns(ops, 0, 40) == 25
    assert tr.merged(ops, 0, 40) == [(0, 15), (20, 30)]
    assert tr.busy_ns(ops, 8, 24) == 7 + 4  # clipped to the window
    assert tr.idle_gaps(ops, 0, 40) == [(15, 20), (30, 40)]


def test_time_grouped_by_kernel_name():
    ops = [S("conv_untiled", 0, 4), S("conv_dma", 4, 10), S("matmul_q16", 10, 13),
           S("copy", 13, 14), S("conv_untiled", 20, 24)]
    assert tr.time_by_prefix(ops, "conv_", 0, 100) == 14
    assert tr.time_by_prefix(ops, "matmul_", 0, 100) == 3
    assert tr.time_by_prefix(ops, "conv_", 0, 22) == 12
    assert tr.top_ops(ops, 0, 100, n=2) == [("conv_untiled", 8), ("conv_dma", 6)]


def test_a_gap_takes_the_label_of_the_innermost_host_span():
    ops = [S("conv", 0, 10), S("conv", 12, 20), S("conv", 30, 40)]
    host = [S("window", 0, 50), S("fetch_logits", 5, 13), S("next_request", 13, 16),
            S("h2d", 16, 28), S("dispatch", 28, 29)]
    out = tr.idle_by_label(ops, host, 0, 50)
    assert out == {"fetch_logits": 2, "h2d": 10, "window": 10}
    assert tr.labels_at([-1, 14, 28.5], host) == [tr.UNLABELLED, "next_request", "dispatch"]


@pytest.mark.parametrize("n", [1, 50])
def test_labels_follow_nested_spans_in_order(n):
    host = [S("window", 0, 100 * n)]
    for i in range(n):
        host += [S("h2d", 100 * i, 100 * i + 10), S("fetch_logits", 100 * i + 50, 100 * i + 90)]
    times = [100 * i + t for i in range(n) for t in (5, 30, 60, 95)]
    want = ["h2d", "window", "fetch_logits", "window"] * n
    assert tr.labels_at(times, host) == want


def test_host_spans_are_aligned_by_the_executions_they_dispatched():
    # host clock: dispatches at 1000, 2000, 3000; the trace's clock runs
    # 10**9 ahead and the second request queued behind the first
    dispatches = [1000.0, 2000.0, 3000.0]
    executions = [10**9 + 1050.0, 10**9 + 2400.0, 10**9 + 3050.0]
    off = tr.align_offset(dispatches, executions)
    assert off == 10**9 + 50
    host = tr.shifted([S("window", 900, 3900), S("dispatch", 1000, 1040)], off)
    assert host[0] == S("window", 10**9 + 950, 10**9 + 3950)
    assert host[1].start == executions[0]  # the request that found the device idle
    with pytest.raises(ValueError):
        tr.align_offset([], executions)
