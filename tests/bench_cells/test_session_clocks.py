"""The nine readers ISSUE 34 adds (a session's own clocks: the writer
thread's states, the pump's waits, the loop thread's and the batcher
thread's CPU): their arithmetic on a hand-made window over hand-made
job records, silence on a program that keeps no such record (the driver
lays these readers over the parent's checkout too), silence at zero
life, and the program's own record holding every key they read."""

from types import SimpleNamespace

import pytest

from benchmark.harness.window import Window, read_metric
from pbs_plus_tpu.server import backup_job
from pbs_plus_tpu.utils import trace

# two jobs of the window and one that is not its own (the warm-up's)
JOBS = {
    "tree-a": dict(writer_pump_wait_s=4.0, writer_cdc_s=1.0,
                   writer_sha_s=0.5, writer_probe_s=0.25,
                   writer_presketch_s=0.25, writer_store_s=2.0,
                   writer_other_s=2.0, writer_cpu_s=5.0, writer_life_s=10.0,
                   pump_rpc_wait_s=6.0, pump_put_wait_s=1.0,
                   pump_join_wait_s=0.5, pump_life_s=10.0,
                   loop_cpu0=100.0, loop_cpu1=104.0),
    "tree-b": dict(writer_pump_wait_s=2.0, writer_cdc_s=3.0,
                   writer_sha_s=0.5, writer_probe_s=0.25,
                   writer_presketch_s=0.25, writer_store_s=2.0,
                   writer_other_s=2.0, writer_cpu_s=6.0, writer_life_s=10.0,
                   pump_rpc_wait_s=3.0, pump_put_wait_s=5.0,
                   pump_join_wait_s=0.5, pump_life_s=10.0,
                   loop_cpu0=103.0, loop_cpu1=109.0),
    "warm-up": dict(writer_pump_wait_s=50.0, writer_life_s=50.0,
                    pump_rpc_wait_s=50.0, pump_put_wait_s=0.0,
                    pump_life_s=50.0, loop_cpu0=0.0, loop_cpu1=99.0),
}
# metric -> its value over tree-a and tree-b
READERS = {
    "writer_pump_wait_pct": 30.0, "writer_cdc_pct": 20.0,
    "writer_store_pct": 20.0, "writer_probe_pct": 2.5,
    "writer_other_pct": 20.0, "pump_rpc_wait_pct": 45.0,
    "pump_put_wait_pct": 30.0,
    # the loop's clock went 100 -> 109 while the two pumps ran, from the
    # first one's start to the second one's end, 15 s apart
    "loop_cpu_pct": 60.0,
    "feeder_cpu_pct": 12.0,
}


@pytest.fixture(autouse=True)
def _own_table():
    trace.clear()
    yield
    trace.clear()


def window(seconds=50.0, ids=("tree-a", "tree-b")):
    jobs = [SimpleNamespace(job_id=i, status="success") for i in ids]
    return Window(seconds=seconds, loop=SimpleNamespace(jobs=jobs),
                  counters={"feeder": {"cpu_s": 6.0, "mask_busy_s": 9.0}})


def closed(jobs=JOBS, dur_s=10.0):
    """The program's records as it would have closed them: tree-a ran
    from 1000 to 1010 on the wall clock, tree-b from 1005 to 1015."""
    starts = {"tree-a": 1000.0, "tree-b": 1005.0, "warm-up": 980.0}
    for job, attrs in jobs.items():
        trace.emit("backup.pump", dur_s, job=job, files=3, **attrs)
        trace.job_records(1)[0]["start"] = starts[job]


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_value(name):
    closed()
    assert read_metric(name, window()) == pytest.approx(READERS[name])


def test_the_writers_states_sum_to_its_life():
    """What the five shares and the two states on the record are checked
    against on the chip: a partition."""
    for attrs in (JOBS["tree-a"], JOBS["tree-b"]):
        assert sum(attrs["writer_" + s] for s in backup_job.WRITER_STATES) \
            == pytest.approx(attrs["writer_life_s"])


@pytest.mark.parametrize("name", sorted(set(READERS) - {"feeder_cpu_pct"}))
def test_reader_is_silent_without_the_record(name, monkeypatch):
    """A program with no table of job records (the parent), one whose
    table holds none of the window's jobs, and one whose records lack
    the clocks: None, and no error."""
    assert read_metric(name, window()) is None          # an empty table
    closed()
    assert read_metric(name, window(ids=("tree-z",))) is None
    assert read_metric(name, Window(seconds=50.0, loop=None,
                                    counters={})) is None
    trace.clear()
    closed({"tree-a": {}, "tree-b": {}})                # PR 29's attrs
    assert read_metric(name, window()) is None
    monkeypatch.delattr(trace, "job_records")
    assert read_metric(name, window()) is None


def test_feeder_cpu_is_silent_without_the_counter():
    old = Window(seconds=50.0, loop=None,
                 counters={"feeder": {"mask_busy_s": 9.0}})
    assert read_metric("feeder_cpu_pct", old) is None
    assert read_metric("feeder_cpu_pct", window(seconds=0.0)) is None


@pytest.mark.parametrize("name", sorted(set(READERS) - {"feeder_cpu_pct"}))
def test_reader_reads_nothing_at_zero_life(name):
    """A job that never ran (its threads' lives are 0, its pump opened
    and closed in one instant): nothing to take a share of."""
    closed({"tree-a": {k: 0.0 for k in JOBS["tree-a"]}}, dur_s=0.0)
    assert read_metric(name, window(ids=("tree-a",))) is None


def test_the_programs_record_holds_every_key_the_readers_read():
    """The names the readers take are the names ``RemoteTreeBackup.run``
    gives: its states, its waits, and the four it spells out."""
    keys = {"writer_" + s for s in backup_job.WRITER_STATES} \
        | {"pump_" + w for w in backup_job.PUMP_WAITS}
    assert set(JOBS["tree-a"]) - keys == {
        "writer_cpu_s", "writer_life_s", "pump_life_s", "loop_cpu0",
        "loop_cpu1"}
    assert keys <= set(JOBS["tree-a"])
