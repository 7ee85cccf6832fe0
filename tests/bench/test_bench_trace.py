"""The trace reduction on hand-built traces (nanoseconds)."""

import numpy as np
import pytest

from bench import tracing
from bench.tracing import Op, Trace


def _trace():
    # window 0..1000; chip 0 runs two overlapping ops and one kernel,
    # chip 1 one long op; one op starts before the window
    dev0 = [Op(-50, 50, "fusion.1", "jit_f"),
            Op(100, 300, "fusion.2", "jit_f"),
            Op(200, 400, "pairwise_lp_call.1", "jit_pairwise_lp_call"),
            Op(700, 800, "pairwise_lp_call.1", "jit_pairwise_lp_call")]
    dev1 = [Op(0, 600, "while.3", "jit_stacked")]
    host = [Op(0, 1000, tracing.WINDOW_EVENT),
            Op(0, 1000, "bench.request"),
            Op(420, 690, "PjitFunction(top_k)"),
            Op(850, 990, "PjitFunction(merge_topk)")]
    progs = {0: [Op(100, 400, "jit_f"), Op(700, 800, "jit_g")],
             1: [Op(0, 600, "jit_stacked")]}
    return Trace(devices={0: dev0, 1: dev1}, programs=progs, host=host)


def test_union_counts_overlap_once_and_clips_to_the_window():
    t = _trace()
    lo, hi = t.window()
    assert (lo, hi) == (0, 1000)
    # [0,50] + [100,400] + [700,800] = 50 + 300 + 100
    assert tracing.busy_ns(t.devices[0], lo, hi) == 450
    assert tracing.busy_ns(t.devices[1], lo, hi) == 600


def test_idle_gaps_are_the_complement_of_the_union():
    t = _trace()
    gaps = tracing.idle_gaps(t.devices[0], 0, 1000)
    assert gaps.tolist() == [[50, 100], [400, 700], [800, 1000]]
    assert gaps[:, 1].sum() - gaps[:, 0].sum() == 1000 - 450


def test_idle_share_ops_programs_and_kernel_time():
    s = tracing.summarize(_trace(), [0, 1])
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == {0: pytest.approx(450e-9), 1: pytest.approx(600e-9)}
    assert s.mean_busy_s == pytest.approx(525e-9)
    # the op that started before the window is not counted
    assert s.ops == {0: 3, 1: 1}
    assert s.programs == {0: 2, 1: 1}
    assert s.kernel_s["pairwise_lp"] == pytest.approx(300e-9)


def test_gaps_are_named_by_the_shortest_host_event_over_them():
    t = _trace()
    gaps = tracing.idle_gaps(t.devices[0], 0, 1000)
    named = dict(tracing.name_gaps(gaps, t.host))
    # 400..700: midpoint 550 lies in the top_k dispatch, not just the
    # request; 800..1000: midpoint 900 in the merge; 50..100: the request
    assert named == {"PjitFunction(top_k)": pytest.approx(300e-9),
                     "PjitFunction(merge_topk)": pytest.approx(200e-9),
                     "bench.request": pytest.approx(50e-9)}


def test_top_ops_rank_by_time_per_chip():
    s = tracing.summarize(_trace(), [0])
    names = [n for n, _ in s.device_ops]
    assert names[0] == "jit_pairwise_lp_call:pairwise_lp_call.1"
    assert s.device_ops[0][1] == pytest.approx(300e-9)
    assert len(s.device_ops) <= 10


def test_a_trace_without_the_window_event_is_an_error():
    t = _trace()
    t.host = [e for e in t.host if e.name != tracing.WINDOW_EVENT]
    with pytest.raises(ValueError):
        t.window()


def test_merged_handles_nesting_and_empty_input():
    iv = np.array([[0, 10], [2, 3], [10, 12], [20, 21]], float)
    assert tracing.merged(iv).tolist() == [[0, 12], [20, 21]]
    assert tracing.merged(np.zeros((0, 2))).shape == (0, 2)


def test_python_threads_name_a_gap_before_runtime_threads():
    host = [Op(0, 100, tracing.WINDOW_EVENT),
            Op(0, 100, "bench.request", thread="python3"),
            Op(40, 60, "ReadSyncFlag", thread="futex-default/7"),
            Op(30, 70, "PjitFunction(top_k)", thread="python3")]
    gaps = np.array([[45.0, 55.0]])
    assert tracing.name_gaps(gaps, host) == [["PjitFunction(top_k)",
                                               pytest.approx(10e-9)]]


def test_op_and_program_names_are_shortened_and_ops_attributed():
    assert tracing.op_name("%fusion.3 = f32[8]{0} fusion(f32[8] %x)") == \
        "fusion.3"
    assert tracing.program_name("jit_pack_sketch(3089894029)") == \
        "jit_pack_sketch"
    ops = [Op(5, 6, "a"), Op(15, 16, "b"), Op(30, 31, "c")]
    tracing.attribute(ops, [Op(10, 20, "jit_g"), Op(0, 10, "jit_f")])
    assert [o.module for o in ops] == ["jit_f", "jit_g", ""]
