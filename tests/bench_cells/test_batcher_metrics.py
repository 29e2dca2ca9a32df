"""The four readers ISSUE 26 adds (the feeder's linger and idle shares,
how often a linger was joined, how many scan dispatches were sharded):
their arithmetic on a hand-made window, silence on a program that keeps
no such counter, and the program's counters holding every key they
read."""

import numpy as np
import pytest

from benchmark.harness import loadgen
from benchmark.harness.window import Window, read_metric

FEEDER = {"linger_s": 8.0, "idle_s": 20.0, "linger_rounds": 400,
          "linger_joined": 30, "rounds": 900}
SCAN = {"dispatches": 800, "mesh_dispatches": 200}

# metric -> (value on the window below, the counters it reads)
READERS = {
    "feeder_linger_pct": (16.0, [("feeder", "linger_s")]),
    "feeder_idle_pct": (40.0, [("feeder", "idle_s")]),
    "feeder_linger_joined_pct": (7.5, [("feeder", "linger_joined"),
                                       ("feeder", "linger_rounds")]),
    "scan_mesh_dispatch_pct": (25.0, [("scan", "mesh_dispatches"),
                                      ("scan", "dispatches")]),
}


def window(seconds=50.0, **layers):
    counters = {"feeder": dict(FEEDER), "scan": dict(SCAN)}
    counters.update(layers)
    return Window(seconds=seconds, loop=None, counters=counters)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_value(name):
    assert read_metric(name, window()) == pytest.approx(READERS[name][0])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_on_a_zero_denominator(name):
    """No seconds in the window, no round that lingered, no dispatch."""
    empty = window(seconds=0.0, feeder=dict(FEEDER, linger_rounds=0),
                   scan=dict(SCAN, dispatches=0))
    assert read_metric(name, empty) is None


def test_no_linger_joined_and_no_dispatch_sharded_read_zero():
    """One session on one chip: a number, 0, and not silence."""
    alone = window(feeder=dict(FEEDER, linger_joined=0),
                   scan=dict(SCAN, mesh_dispatches=0))
    assert read_metric("feeder_linger_joined_pct", alone) == 0.0
    assert read_metric("scan_mesh_dispatch_pct", alone) == 0.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_silent_on_a_program_without_the_counters(name):
    """A program from before the counters (the driver lays these readers
    over the parent's checkout too): None, and no error."""
    old = Window(seconds=50.0, loop=None, counters={
        "feeder": {"rounds": 5, "mask_rows": 3}, "scan": {"bytes": 1}})
    assert read_metric(name, old) is None
    assert read_metric(name, Window(seconds=50.0, loop=None,
                                    counters={})) is None


def test_on_the_parent_only_the_joined_share_is_silent():
    """PR 25's feeder has the clocks and ``rolling_hash.stats`` the mesh
    count; only ``linger_rounds`` / ``linger_joined`` are new."""
    parent = window(feeder={"linger_s": 8.0, "idle_s": 20.0, "rounds": 900})
    got = {name: read_metric(name, parent) for name in READERS}
    assert got["feeder_linger_joined_pct"] is None
    assert all(v is not None for k, v in got.items()
               if k != "feeder_linger_joined_pct")


def test_the_programs_counters_hold_every_key_the_readers_use():
    """Two ``device_counters()`` snapshots around one lone scan through
    the process's feeder: the deltas carry every counter a reader asks
    for, and the lone round lingered unjoined."""
    from pbs_plus_tpu.chunker import ChunkerParams
    from pbs_plus_tpu.models.feeder import get_feeder
    before = loadgen.device_counters()
    data = np.random.default_rng(26).integers(0, 256, 70_000,
                                              dtype=np.uint8)
    get_feeder().candidate_hits(data, np.zeros(63, np.uint8),
                                ChunkerParams(avg_size=4 << 10))
    deltas = loadgen.counter_deltas(before, loadgen.device_counters())
    for name, (_, keys) in READERS.items():
        for layer, key in keys:
            assert isinstance(deltas[layer].get(key), (int, float)), \
                (name, layer, key)
    assert deltas["feeder"]["rounds"] == 1
    assert deltas["feeder"]["linger_rounds"] == 1
    assert deltas["feeder"]["linger_joined"] == 0
    assert deltas["scan"]["dispatches"] == 1
    got = {name: read_metric(name, Window(seconds=1.0, loop=None,
                                          counters=deltas))
           for name in READERS}
    assert got["feeder_linger_joined_pct"] == 0.0
    assert got["scan_mesh_dispatch_pct"] == 0.0
    assert got["feeder_linger_pct"] > 0.0 and got["feeder_idle_pct"] >= 0.0
