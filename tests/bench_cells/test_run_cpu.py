"""The harness end to end without a chip: it refuses to measure the CPU;
with the look for a chip skipped, a sound run of a tiny cell reads
correct, the control reads not correct, and a timed path broken
underneath reads not correct."""

import asyncio
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TREE = {"kind": "lognormal", "mu": 9.48, "sigma": 2.46, "own_files": 24,
        "common_files": 16, "dirs": 2, "compressible_every": 2}


def tiny_cell():
    from benchmark.harness import loadgen
    cfg = loadgen.check_config("tiny", {
        "server": {"chunker": "tpu", "chunk_avg": 65536,
                   "max_concurrent": 16, "dedup_index_mb": -1},
        "meta_chunk_avg": 65536, "agents": 2, "trees_per_agent": 1,
        "tree": TREE, "warm_tree": dict(TREE, own_files=4, common_files=2),
        "warm_shapes": {"scan_rows": [1, 4], "scan_seg_kib": [64, 256, 1024, 4096],
                        "sha_classes": [[16, 8], [16, 64]]},
        "index_preload_digests": 500})
    traffic = loadgen.check_traffic("burst", {"arrival": "burst",
                                              "agents": "all"})
    return loadgen.Cell("fanin8-mixed.burst", 1, "tiny", "burst", cfg,
                        traffic)


def drive(tmp_path, **kw):
    """The rest of a run, with the look for a chip skipped."""
    import jax

    from benchmark import run
    return asyncio.run(run.run_cell(
        tiny_cell(), seed=2**31 + 7, seconds=30.0, trace=False,
        work=str(tmp_path), devices=jax.devices()[:1], **kw))


def test_without_a_tpu_it_says_why_and_exits_before_any_setup():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "fanin8-mixed.burst", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and p.stdout.strip() == ""


def test_sound_run_is_correct_and_the_control_is_not(tmp_path):
    from benchmark.harness import reference
    result = drive(tmp_path, controls={"window32": reference.control_cuts})
    assert result is not None, "a program compiled inside the window"
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] == 2 and result["failed"] == 0
    assert all(c["value"] == c["limit"] == 0
               for c in result["compared"].values())
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"setup_s", "ingest_mib_s"}
    assert result["metrics"]["ingest_mib_s"]["value"] > 0
    control = result["controls"]["window32"]
    assert control["correct"] is False
    assert control["compared"]["cut_mismatches"]["value"] >= 2
    assert control["compared"]["digest_mismatches"]["value"] == 0


@pytest.mark.parametrize("fault", ["digest", "cut"])
def test_an_answer_altered_where_it_is_produced_reads_not_correct(
        tmp_path, monkeypatch, fault):
    from pbs_plus_tpu.models.dedup import TpuChunker
    from pbs_plus_tpu.ops import sha256
    if fault == "digest":
        real = sha256.sha256_chunks

        def flipped(chunks, **kw):
            out = real(chunks, **kw)
            return [bytes([out[0][0] ^ 1]) + out[0][1:]] + out[1:]
        monkeypatch.setattr(sha256, "sha256_chunks", flipped)
    else:
        real = TpuChunker._candidates

        def first_dropped(self, data):
            return real(self, data)[1:]
        monkeypatch.setattr(TpuChunker, "_candidates", first_dropped)
    result = drive(tmp_path)
    assert result is not None and result["correct"] is False
    name = {"digest": "digest_mismatches", "cut": "cut_mismatches"}[fault]
    assert result["compared"][name]["value"] > 0
