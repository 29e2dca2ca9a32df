"""The reduction from trace events to metrics, on a small synthetic
event list and on one recorded from the chip."""

import json
import os

import pytest

from benchmark.harness import tracereduce as tr

PEAKS = {"devices": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}}
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def events():
    """One device: three programs, two of them overlapping and one
    nested in another; a gap of 0.5 s while the feeder packed, 0.25 s
    more while only a writer waited, and a tail with nobody feeding."""
    modules = [["jit__candidate_mask_impl(123)", 0.0, 1.0],
               ["jit__sha256_scan_impl(456)", 2.0, 2.6],
               ["jit__sha256_scan_impl(456)", 2.4, 3.0],    # overlapping
               ["jit__lookup(789)", 2.1, 2.2]]              # nested
    host = [["bench.feeder.dispatch_masks", 1.0, 1.5],
            ["bench.chunker.feed", 0.0, 1.75],
            ["bench.feeder.dispatch_sha", 1.9, 3.0],
            ["bench.chunker.feed", 3.0, 3.5],
            ["bench.chunker.feed", 4.0, 4.0]]               # empty: ignored
    return {"devices": {"/device:TPU:0": {tr.MODULES_LINE: modules}},
            "host": host}


def test_interval_arithmetic():
    assert tr.union([(2, 3), (0, 1), (0.5, 1.5), (1.5, 1.6), (5, 5)]) == \
        [(0, 1.6), (2, 3)]
    assert tr.total([(0, 1.5), (2, 3)]) == 2.5
    assert tr.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert tr.subtract([(0, 5)], [(1, 2), (3, 4)]) == [(0, 1), (2, 3), (4, 5)]
    assert tr.gaps([(1, 2)], 0, 3) == [(0, 1), (2, 3)]


def test_busy_union_idle_share_and_program_totals():
    out = tr.reduce(events(), window_s=4.0, fed_bytes=819_000_000,
                    device_kind="TPU v5 lite", peaks=PEAKS)
    assert out["busy_s"] == pytest.approx(2.0)       # 1.0 + union(2.0..3.0)
    assert out["scan_phase_idle_pct"] == pytest.approx(50.0)
    assert [n for n, _ in out["device_ops"]] == [
        "jit__sha256_scan_impl", "jit__candidate_mask_impl", "jit__lookup"]
    assert dict(out["device_ops"])["jit__sha256_scan_impl"] == \
        pytest.approx(1.2)               # per-program totals, not the union
    # 819 MB at 819 GB/s is 1 ms of the chip; it was busy for 2 s
    assert out["scan_roofline"] == pytest.approx(100 * 0.001 / 2.0)
    assert 0 < out["scan_roofline"] < 100


def test_idle_gaps_are_charged_to_the_most_specific_host_activity():
    out = tr.reduce(events(), window_s=4.0, fed_bytes=1,
                    device_kind="TPU v5 lite", peaks=PEAKS)
    gaps = dict(out["idle_gaps"])
    # device idle 1.0..2.0 and 3.0..4.0 (the host's last stamp)
    assert gaps["bench.feeder.dispatch_masks"] == pytest.approx(0.5)
    assert gaps["bench.feeder.dispatch_sha"] == pytest.approx(0.1)
    assert gaps["bench.chunker.feed"] == pytest.approx(0.25 + 0.5)
    assert gaps[tr.NO_LABEL] == pytest.approx(0.15 + 0.5)
    assert sum(gaps.values()) == pytest.approx(2.0)


def test_no_device_operation_means_nothing_to_read():
    empty = {"devices": {"/device:TPU:0": {tr.MODULES_LINE: []}},
             "host": []}
    out = tr.reduce(empty, window_s=1.0, fed_bytes=10,
                    device_kind="TPU v5 lite", peaks=PEAKS)
    assert out == {"busy_s": 0.0, "window_s": 1.0}
    assert "scan_roofline" not in out       # never a 0 for a share of a peak


def test_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(tr.PeakUnknown):
        tr.reduce(events(), window_s=4.0, fed_bytes=1,
                  device_kind="TPU v9 imaginary", peaks=PEAKS)
    assert tr.peak_bytes_per_s(tr.load_peaks(), "TPU v5 lite") == 819e9


def test_reduction_of_a_trace_recorded_on_the_chip():
    path = os.path.join(DATA, "reduction_input.json")
    with open(path, encoding="utf-8") as f:
        rec = json.load(f)
    out = tr.reduce(rec["events"], window_s=rec["window_s"],
                    fed_bytes=rec["fed_bytes"],
                    device_kind=rec["device_kind"], peaks=tr.load_peaks())
    for key, want in rec["expect"].items():
        assert out[key] == pytest.approx(want, rel=1e-9), key
    assert 0 < out["busy_s"] <= out["window_s"]
    assert 0 < out["scan_roofline"] < 100
    assert out["device_ops"] and out["idle_gaps"]
