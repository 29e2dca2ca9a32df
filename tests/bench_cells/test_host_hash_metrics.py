"""The two readers of the host SHA-256 engine's counters (ISSUE 25):
their arithmetic on a hand-made window, silence on a program that keeps
no such counter, and the program's ``sha256.stats`` holding every key
they read."""

import pytest

from benchmark.harness import loadgen
from benchmark.harness.window import Window, read_metric

SHA = {"bytes": 100, "host_bytes": 300, "host_s": 2.5, "host_batches": 4,
       "host_rows": 9, "device_s": 1.0}

# metric -> (value on the window below, the sha counters it reads)
READERS = {
    "sha_host_pct": (5.0, ["host_s"]),
    "sha_host_bytes_pct": (75.0, ["host_bytes", "bytes"]),
}


def window(seconds=50.0, **sha):
    return Window(seconds=seconds, loop=None,
                  counters={"sha": dict(SHA, **sha)})


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_value(name):
    assert read_metric(name, window()) == pytest.approx(READERS[name][0])


def test_every_hashed_byte_on_the_host_reads_100_and_none_reads_0():
    assert read_metric("sha_host_bytes_pct", window(bytes=0)) == 100.0
    assert read_metric("sha_host_bytes_pct", window(host_bytes=0)) == 0.0
    assert read_metric("sha_host_pct", window(host_s=0.0)) == 0.0


@pytest.mark.parametrize("name,empty", [
    ("sha_host_pct", window(seconds=0.0)),
    ("sha_host_bytes_pct", window(bytes=0, host_bytes=0)),
])
def test_reader_reads_nothing_on_a_zero_denominator(name, empty):
    """No seconds in the window, or nothing hashed: nothing to read."""
    assert read_metric(name, empty) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_silent_on_a_program_without_the_counters(name):
    """The parent commit's ``sha256.stats`` has no ``host_*`` key (the
    driver lays these readers over its checkout too): None, no error."""
    old = Window(seconds=50.0, loop=None, counters={
        "sha": {"rows": 1, "bytes": 7, "device_s": 1.0},
        "feeder": {"sha_streams": 1}})
    assert read_metric(name, old) is None
    assert read_metric(name, Window(seconds=50.0, loop=None,
                                    counters={})) is None


def test_the_programs_counters_hold_every_key_the_readers_use():
    """Two ``device_counters()`` snapshots around one batch on each
    engine: the deltas carry every counter a reader asks for."""
    from pbs_plus_tpu.ops import sha256
    before = loadgen.device_counters()
    sha256.sha256_chunks([b"abc" * 1000, b"de"])
    sha256.sha256_chunks_device([b"abc"])
    deltas = loadgen.counter_deltas(before, loadgen.device_counters())
    for name, (_, keys) in READERS.items():
        for key in keys:
            assert isinstance(deltas["sha"].get(key), (int, float)), \
                (name, key)
    assert deltas["sha"]["host_batches"] == 1
    assert deltas["sha"]["host_rows"] == 2
    assert deltas["sha"]["host_bytes"] == 3002 and deltas["sha"]["bytes"] == 3
    got = {name: read_metric(name, Window(seconds=1.0, loop=None,
                                          counters=deltas))
           for name in READERS}
    assert got["sha_host_bytes_pct"] == pytest.approx(100 * 3002 / 3005)
    assert got["sha_host_pct"] == pytest.approx(
        100.0 * deltas["sha"]["host_s"]) and got["sha_host_pct"] > 0.0
