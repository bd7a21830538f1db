"""The trace reduction on a small recorded trace (tests/bench/data).

In its 1 s window device 0 runs [10.0, 10.30], [10.50, 10.70], [10.80,
10.90] and [10.95, 11.0]: busy 0.65 s, idle 0.35 s, in gaps of 0.20 s
(around 10.40: host in ``step``), 0.10 s (10.75: ``step``) and 0.05 s
(10.925: ``step``).  Two ``_gemv_placed_kernel`` calls run in the decode
step (0.20 s + 0.10 s) and one in a prefill (0.10 s).
"""
import json
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from bench import trace

DATA = Path(__file__).parent / "data" / "small_trace.json"


@pytest.fixture
def tr():
    return trace.Trace.from_json(json.loads(DATA.read_text()))


def test_idle_share_is_one_minus_busy_union(tr):
    assert trace.busy_seconds(tr) == pytest.approx(0.65)
    assert trace.idle_share(tr) == pytest.approx(0.35)


@pytest.mark.parametrize("module, want", [
    (None, 0.40), (r"_step_fn", 0.30), (r"_prefill", 0.10)])
def test_kernel_time_by_name_and_program(tr, module, want):
    assert trace.op_seconds(tr, r"_gemv_placed_kernel", module) == \
        pytest.approx(want)


def test_counts_of_kernel_calls_and_programs(tr):
    assert trace.op_count(tr, r"_gemv_placed_kernel") == 3
    # the step that began before the window is not counted
    assert trace.module_count(tr, r"_step_fn") == 2


def test_idle_gaps_are_named_by_host_phase(tr):
    gaps = trace.idle_gaps(tr)
    assert [g[0] for g in gaps] == ["step", "step", "step"]
    assert [g[1] for g in gaps] == pytest.approx([0.20, 0.10, 0.05])


def test_top_ops_sum_clipped_device_time(tr):
    top = dict(trace.top_ops(tr))
    assert top["custom-call.7"] == pytest.approx(0.30)
    assert top["fusion.1"] == pytest.approx(0.10)
    assert list(top)[0] == "custom-call.7"


def test_no_device_means_no_idle_share(tr):
    tr.devices = []
    assert trace.idle_share(tr) is None
    assert trace.idle_gaps(tr) == []
