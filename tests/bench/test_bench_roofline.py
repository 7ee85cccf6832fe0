"""The roofline yardstick: work and bytes, the bound, the peaks table."""

import json

import pytest

from bench import roofline
from bench.harness import Window
from bench.spec import Benchmark
from bench.tracing import Summary


def test_pairwise_lp_work_counts_unpadded_float32_traffic():
    flops, nbytes = roofline.pairwise_lp_work(q=64, n=1_000_000, w=768)
    assert flops == 2 * 64 * 1_000_000 * 768
    assert nbytes == 4 * (1_000_000 * 768 + 64 * 768 + 64 * 1_000_000
                          + 1_000_000 + 64)


def test_least_time_is_the_larger_bound():
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert roofline.least_seconds(1000.0, 10.0, peak) == (10.0, "compute")
    assert roofline.least_seconds(10.0, 1000.0, peak) == (100.0, "memory")


def test_the_chip_is_in_the_table_with_its_source():
    p = roofline.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_an_unknown_device_is_an_error(tmp_path):
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")
    f = tmp_path / "peaks.json"
    f.write_text(json.dumps({"devices": {}}))
    with pytest.raises(KeyError):
        roofline.peaks("TPU v5 lite", f)


def _window(kernel_s, batches, live=1_000_000):
    s = Summary(window_s=10.0, chips=[0], busy_s={0: 1.0}, ops={0: 100},
                programs={0: 10}, kernel_s={"pairwise_lp": kernel_s},
                device_ops=[], idle_gaps=[], bounds_ns=(0, 1e10))
    return Window(trace=s, traced_batches=batches, live_rows=live,
                  packed_width=768, peak=roofline.peaks("TPU v5 lite"),
                  chips=1)


def test_roofline_reader_divides_least_time_by_kernel_time():
    read = Benchmark().metric_reader("pairwise_lp_roofline")
    _, nbytes = roofline.pairwise_lp_work(64, 1_000_000, 768)
    least = nbytes / 819e9
    # one whole batch and half of another that straddles the window
    w = _window(kernel_s=3 * least, batches=[(64, 1.0), (64, 0.5)])
    assert read(w) == pytest.approx(100.0 * 1.5 / 3)
    assert read(_window(0.0, [(64, 1.0)])) is None


def test_mfu_reader_bounds_the_kernel_share_from_below():
    bench = Benchmark()
    w = _window(kernel_s=0.01, batches=[(64, 1.0)])
    mfu = bench.metric_reader("knn_mfu")(w)
    assert 0 < mfu < bench.metric_reader("pairwise_lp_roofline")(w)


def test_four_chip_readers_divide_like_for_like():
    """Kernel time summed over four chips against one chip's peak: four
    chips that each spend a quarter of one chip's kernel time on a quarter
    of its work read that chip's share.  ``knn_mfu`` divides by the window
    times the four chips."""
    bench = Benchmark()
    peak = roofline.peaks("TPU v5 lite")
    one = _window(kernel_s=0.02, batches=[(64, 1.0)], live=4_000_000)
    chips = [0, 1, 2, 3]
    s = Summary(window_s=10.0, chips=chips, busy_s=dict.fromkeys(chips, 1.0),
                ops=dict.fromkeys(chips, 100),
                programs=dict.fromkeys(chips, 10),
                kernel_s={"pairwise_lp": 0.02}, device_ops=[], idle_gaps=[],
                bounds_ns=(0, 1e10))
    four = Window(trace=s, traced_batches=[(64, 1.0)], live_rows=4_000_000,
                  packed_width=768, peak=peak, chips=4)
    for name in ("pairwise_lp_roofline", "knn_mfu"):
        assert bench.metric_reader(name)(four) is not None
    assert bench.metric_reader("pairwise_lp_roofline")(four) == \
        pytest.approx(bench.metric_reader("pairwise_lp_roofline")(one))
    assert bench.metric_reader("knn_mfu")(four) == \
        pytest.approx(bench.metric_reader("knn_mfu")(one) / 4)
