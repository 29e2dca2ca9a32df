"""The deployment ``single-tree`` (ISSUE 26) without a chip, at a small
size: one agent, one job at a time, nothing common to other machines.
Through ``run.run_cell`` a sound run reads correct and PR 23's control
does not; the jobs never overlap; no scan round carries more rows than
one session has streams; and the data files of both new cells load."""

import asyncio
import json
import os

import pytest

from benchmark.harness import loadgen

TREE = {"kind": "lognormal", "mu": 9.48, "sigma": 2.46, "own_files": 40,
        "common_files": 0, "dirs": 4, "compressible_every": 2}
STREAMS_PER_SESSION = 2             # payload and metadata
MANIFEST = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "BENCHMARK.json")


def small_cell():
    """The cell's own traffic file over the configuration cut to the
    CPU: 64 KiB chunks, three trees of 40 files (the ladder's largest is
    3.1 MiB, dozens of chunks)."""
    cfg = loadgen.check_config("single-tree-small", {
        "server": {"chunker": "tpu", "chunk_avg": 65536,
                   "max_concurrent": 16, "dedup_index_mb": -1},
        "meta_chunk_avg": 65536, "agents": 1, "trees_per_agent": 3,
        "tree": TREE, "warm_tree": dict(TREE, own_files=4, dirs=2),
        "warm_shapes": {"scan_rows": [1, 4],
                        "scan_seg_kib": [64, 256, 1024, 4096]},
        "index_preload_digests": 500})
    traffic = loadgen.load_cell("single-tree.serial").traffic
    return loadgen.Cell("single-tree.serial", 1, "single-tree-small",
                        "serial", cfg, traffic)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One run of the small cell with the control beside the reference:
    the result object and the lines it said on the way."""
    import contextlib
    import io

    import jax

    from benchmark import run as bench_run
    from benchmark.harness import reference
    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        result = asyncio.run(bench_run.run_cell(
            small_cell(), seed=2**31 + 26, seconds=120.0, trace=False,
            work=str(tmp_path_factory.mktemp("single-tree")),
            devices=jax.devices()[:1],
            controls={"window32": reference.control_cuts}))
    assert result is not None, "a program compiled inside the window"
    lines = [json.loads(ln) for ln in said.getvalue().splitlines()
             if ln.startswith("{")]
    return result, {ln["phase"]: ln for ln in lines if "phase" in ln}


def test_sound_run_is_correct_on_all_eight_comparisons(run):
    result, _ = run
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] == 3 and result["failed"] == 0
    assert len(result["compared"]) == 8
    assert all(c["value"] == c["limit"] == 0
               for c in result["compared"].values())
    assert set(result["metrics"]) == {"setup_s", "ingest_mib_s"}
    assert result["metrics"]["ingest_mib_s"]["value"] > 0


def test_the_control_reads_not_correct(run):
    control = run[0]["controls"]["window32"]
    assert control["correct"] is False
    assert control["compared"]["cut_mismatches"]["value"] >= 3
    assert control["compared"]["digest_mismatches"]["value"] == 0


def test_jobs_never_overlap(run):
    """Job k+1 is enqueued no earlier than job k is done."""
    jobs = run[1]["compare"]["jobs_from_t0_s"]
    assert [j[0] for j in jobs] == [f"bench-agent-00-{k}" for k in range(3)]
    for (_, _, done), (_, enqueued, _) in zip(jobs, jobs[1:]):
        assert enqueued >= done
    assert run[1]["window"]["drained"] is True


def test_no_scan_round_is_wider_than_one_session(run):
    """Deltas over the window, since the feeder is the process's and
    ``max_mask_batch`` is a gauge: a round's rows are one session's
    streams at most, and no linger is joined more often than it is
    taken."""
    feeder = run[1]["window"]["counters"]["feeder"]
    assert feeder["mask_dispatches"] > 0
    assert feeder["mask_rows"] <= STREAMS_PER_SESSION \
        * feeder["mask_dispatches"]
    assert 0 <= feeder["linger_joined"] <= feeder["linger_rounds"] \
        <= feeder["rounds"]
    assert feeder["linger_rounds"] > 0


@pytest.mark.parametrize("name,chips,agents", [
    ("single-tree.serial", 1, 1), ("fanin8-mixed.burst-x4", 4, 8)])
def test_cell_files_load(name, chips, agents):
    cell = loadgen.load_cell(name)
    assert cell.chips == chips and cell.config["agents"] == agents
    assert cell.config["server"]["chunk_avg"] == 4 << 20
    trees = {f"agent-{a:02d}": list(range(cell.config["trees_per_agent"]))
             for a in range(agents)}
    backlog = loadgen.plan_backlog(trees, cell.traffic)
    assert len(backlog) == agents
    assert all(len(q) == cell.config["trees_per_agent"]
               for q in backlog.values())


def test_the_x4_cell_is_the_one_chip_cell_on_four_chips():
    one, four = (loadgen.load_cell(n) for n in
                 ("fanin8-mixed.burst", "fanin8-mixed.burst-x4"))
    # BENCHMARK.json admits a pair of config and traffic once, so the
    # four-chip cell's traffic is a file of its own: the same parameters.
    def params(traffic):
        return {k: v for k, v in traffic.items() if k != "why"}
    assert one.config_name == four.config_name
    assert (one.traffic_name, four.traffic_name) == ("burst", "burst-x4")
    assert (one.config, params(one.traffic)) == \
        (four.config, params(four.traffic))
    assert (one.chips, four.chips) == (1, 4)


def test_no_pair_of_config_and_traffic_is_given_twice():
    with open(MANIFEST, encoding="utf-8") as f:
        manifest = json.load(f)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_single_tree_is_one_machine_at_upstreams_widths():
    cfg = loadgen.load_cell("single-tree.serial").config
    sizes = loadgen.ladder(cfg["tree"]["own_files"], cfg["tree"]["mu"],
                           cfg["tree"]["sigma"])
    assert cfg["agents"] == 1 and cfg["tree"]["common_files"] == 0
    assert cfg["trees_per_agent"] >= 6
    assert sizes.max() > 40 << 20            # 41.6 MiB: ten writes in a row
    big = sizes[sizes >= 4 << 20]
    assert len(big) == 10 and 0.49 < big.sum() / sizes.sum() < 0.51
    assert (sizes < 64 << 10).sum() == 761
