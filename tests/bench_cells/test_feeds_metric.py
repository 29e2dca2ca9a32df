"""The reader ISSUE 27 adds (``scan_feeds_per_row``: how many of a
stream's writes one row of a candidate scan carries): its arithmetic on
a hand-made window, silence on a program that keeps no such counter or
scanned no row, and the program's counters holding the keys it reads."""

import numpy as np
import pytest

from benchmark.harness import loadgen
from benchmark.harness.window import Window, read_metric

NAME = "scan_feeds_per_row"


def window(**feeder):
    return Window(seconds=50.0, loop=None, counters={"feeder": feeder})


@pytest.mark.parametrize("feeds,rows,value", [
    (2120, 62, 2120 / 62),      # a tree's writes in its segments
    (5000, 5000, 1.0),          # every write a round trip of its own
    (7, 2, 3.5)])
def test_reader_value(feeds, rows, value):
    got = read_metric(NAME, window(mask_feeds=feeds, mask_rows=rows,
                                   mask_dispatches=rows))
    assert got == pytest.approx(value)


@pytest.mark.parametrize("counters", [
    {},                                             # no feeder at all
    {"feeder": {}},
    {"feeder": {"mask_rows": 6000, "mask_dispatches": 5000,
                "rounds": 5000}},                   # the parent's feeder
], ids=["no_layer", "empty_layer", "parent"])
def test_reader_is_silent_on_a_program_without_the_counter(counters):
    """The driver lays this reader over the parent's checkout too: None
    there, and no error."""
    assert read_metric(NAME, Window(seconds=50.0, loop=None,
                                    counters=counters)) is None


def test_reader_reads_nothing_when_no_row_was_scanned():
    assert read_metric(NAME, window(mask_feeds=0, mask_rows=0)) is None


def test_the_programs_counters_hold_the_keys_and_count_a_streams_writes():
    """Two ``device_counters()`` snapshots around one short stream
    through the process's feeder: ten writes, one row."""
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.models.dedup import TpuChunker
    before = loadgen.device_counters()
    data = np.random.default_rng(27).integers(0, 256, 70_000,
                                              dtype=np.uint8).tobytes()
    ch = TpuChunker(ChunkerParams(avg_size=4 << 10))
    for off in range(0, len(data), 7_000):
        ch.feed(data[off:off + 7_000])
    assert ch.finalize()[-1] == len(data)
    deltas = loadgen.counter_deltas(before, loadgen.device_counters())
    assert deltas["feeder"]["mask_rows"] == 1
    assert deltas["feeder"]["mask_feeds"] == 10
    assert read_metric(NAME, Window(seconds=1.0, loop=None,
                                    counters=deltas)) == 10.0
