"""The nine readers of the program's own phase clocks and queue waits
(ISSUE 24): each on a hand-made window, each silent on a program that
keeps no such counter, and the program's counters holding every key
they read."""

import numpy as np
import pytest

from benchmark.harness import loadgen
from benchmark.harness.window import Window, read_metric

SCAN = {"pack_s": 1.0, "h2d_s": 0.5, "device_s": 0.25, "d2h_s": 1.5,
        "unpack_s": 2.0}
SHA = {"pack_s": 3.0, "h2d_s": 2.0, "device_s": 4.0, "d2h_s": 0.5,
       "unpack_s": 0.1}
FEEDER = {"mask_wait_s": 0.6, "mask_rows": 300, "sha_wait_s": 9.0,
          "sha_streams": 20}

# metric -> (value on the window below, the counters it reads)
READERS = {
    "scan_pack_pct": (2.0, [("scan", "pack_s")]),
    "scan_copy_pct": (4.0, [("scan", "h2d_s"), ("scan", "d2h_s")]),
    "scan_device_pct": (0.5, [("scan", "device_s")]),
    "scan_unpack_pct": (4.0, [("scan", "unpack_s")]),
    "sha_pack_pct": (6.0, [("sha", "pack_s")]),
    "sha_copy_pct": (5.0, [("sha", "h2d_s"), ("sha", "d2h_s")]),
    "sha_device_pct": (8.0, [("sha", "device_s")]),
    "feeder_scan_queue_ms": (2.0, [("feeder", "mask_wait_s"),
                                   ("feeder", "mask_rows")]),
    "feeder_sha_queue_ms": (450.0, [("feeder", "sha_wait_s"),
                                    ("feeder", "sha_streams")]),
}


def window(seconds=50.0, **layers):
    counters = {"scan": dict(SCAN), "sha": dict(SHA),
                "feeder": dict(FEEDER)}
    counters.update(layers)
    return Window(seconds=seconds, loop=None, counters=counters)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_value(name):
    assert read_metric(name, window()) == pytest.approx(READERS[name][0])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_on_a_zero_denominator(name):
    """No seconds in the window, or no request served: nothing to read."""
    empty = window(seconds=0.0, feeder=dict(FEEDER, mask_rows=0,
                                            sha_streams=0))
    assert read_metric(name, empty) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_silent_on_a_program_without_the_counters(name):
    """The parent commit's dicts hold none of the new keys (the driver
    lays these readers over its checkout too): None, and no error."""
    old = Window(seconds=50.0, loop=None, counters={
        "scan": {"dispatches": 3, "bytes": 1, "padded_bytes": 2},
        "sha": {"rows": 1}, "feeder": {"mask_rows": 3, "sha_streams": 1}})
    assert read_metric(name, old) is None
    assert read_metric(name, Window(seconds=50.0, loop=None,
                                    counters={})) is None


def test_the_programs_counters_hold_every_key_the_readers_use():
    """Two ``device_counters()`` snapshots around one small feeder round:
    their deltas carry every counter a reader asks for, as a number."""
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.models.feeder import get_feeder
    before = loadgen.device_counters()
    data = np.random.default_rng(24).integers(0, 256, 70_000,
                                              dtype=np.uint8)
    feeder = get_feeder()
    feeder.candidate_hits(data, np.zeros(63, np.uint8),
                          ChunkerParams(avg_size=4 << 10))
    feeder.sha256_batch([data[:5000].tobytes(), b"abc"])
    deltas = loadgen.counter_deltas(before, loadgen.device_counters())
    for name, (_, keys) in READERS.items():
        for layer, key in keys:
            assert isinstance(deltas[layer].get(key), (int, float)), \
                (name, layer, key)
    assert deltas["scan"]["dispatches"] == 1
    assert deltas["sha"]["dispatches"] == 2 and deltas["sha"]["slabs"] == 1
    assert deltas["feeder"]["mask_rows"] == 1
    assert deltas["feeder"]["sha_streams"] == 1
    for name in READERS:
        value = read_metric(name, Window(seconds=1.0, loop=None,
                                         counters=deltas))
        assert value is not None and value >= 0.0, name
