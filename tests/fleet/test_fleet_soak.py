"""Fleet-scale soak (ISSUE 7 tentpole; docs/fleet.md): hundreds to two
thousand simulated agents speak real aRPC over plain-TCP loopback
through MuxConnection + AgentsManager, each running a small synthetic
backup through the real jobs plane (fair dequeue, weighted shares,
breakers, bounded queue) into a real datastore — plus the ISSUE 19
mixed-traffic profile: multiple backup waves per agent, keepalive
churn, restore/verify/sync lanes through the same execution slots, and
all five hostile profiles attacking concurrently.

The default pytest loop runs N=100 (seconds on a 1-core host); the
N=500 acceptance profile is ``slow``-marked and also reachable via
``PBS_PLUS_FLEET=1``; the N=2000 survival profile needs BOTH:

    PBS_PLUS_FLEET=1 python -m pytest tests/fleet/ -q -m slow
"""

import os

import pytest

from pbs_plus_tpu.server import metrics
from pbs_plus_tpu.server.fleetsim import FleetConfig, run_fleet
from pbs_plus_tpu.utils import trace

FULL = bool(os.environ.get("PBS_PLUS_FLEET"))


def _assert_traced(rep, n_agents: int, d: dict) -> None:
    """ISSUE 12 acceptance over the soak: (a) the report's percentiles
    derive from the shared /metrics histograms, (b) at least one
    complete job trace nests enqueue→grant→session-open→per-stage
    ingest→publish with agent-side spans parented via mux metadata."""
    # (a) /metrics exports the histograms the report derived from
    expo = metrics.render_histograms()
    assert 'pbs_plus_job_enqueue_to_publish_seconds_bucket{' in expo
    assert 'pbs_plus_session_open_seconds_bucket{' in expo
    h = metrics.HISTOGRAMS["pbs_plus_job_enqueue_to_publish_seconds"]
    key = (("kind", "backup"),)
    now = h.snapshot()[key]
    base = rep.hist_baseline[
        "pbs_plus_job_enqueue_to_publish_seconds"].get(key, {"count": 0})
    # every published backup fed exactly one observation this soak
    assert now["count"] - base["count"] == d["published"]

    # (b) one complete, correctly-nested job trace in the ring
    by_trace: dict = {}
    for r in trace.recent():
        by_trace.setdefault(r["trace"], {})[r["span"]] = r
    want = {"job", "job.queue_wait", "job.execute", "backup.session_open",
            "backup.publish", "ingest.cdc", "ingest.sha"}
    complete = 0
    for spans in by_trace.values():
        names = {s["name"] for s in spans.values()}
        if not want <= names:
            continue
        agent_side = [s for s in spans.values()
                      if s["name"] == "rpc.serve"
                      and s.get("attrs", {}).get("method",
                                                 "").startswith("agentfs.")]
        if not agent_side:
            continue
        root = next(s for s in spans.values() if s["name"] == "job")
        assert root["parent"] == ""
        for s in spans.values():
            if s["name"] in ("job.queue_wait", "job.execute"):
                assert s["parent"] == root["span"]
        execute = next(s for s in spans.values()
                       if s["name"] == "job.execute")
        for s in spans.values():
            if s["name"] in ("backup.session_open", "backup.publish"):
                assert s["parent"] == execute["span"]
        # agent-side agentfs serves parent under the server-side job
        # trace — the context crossed the mux in the call metadata
        for s in agent_side:
            assert s["parent"] in spans
        complete += 1
    assert complete >= 1, (
        f"no complete job trace among {len(by_trace)} traces in the ring")


def _soak(tmp_path, n_agents: int) -> dict:
    trace.clear()       # ring assertions below cover THIS soak only
    cfg = FleetConfig(n_agents=n_agents, tenants=8, max_concurrent=8,
                      max_queued=2 * n_agents)
    rep = run_fleet(str(tmp_path / "ds"), cfg)
    d = rep.to_dict()

    # every admitted job published; nothing left failed
    assert d["published"] == n_agents, rep.failures
    assert not rep.failures

    # latency percentiles are measured and ordered — derived from the
    # shared /metrics histograms (bucket-diff quantiles, ISSUE 12; the
    # per-job completion count is pinned against the histogram in
    # _assert_traced, not a duplicate sample list)
    assert 0 < d["enqueue_to_publish_p50_s"] <= d["enqueue_to_publish_p99_s"]
    assert 0 < d["session_open_p50_s"] <= d["session_open_p99_s"]

    # bounded queues held their bounds throughout (sampler witness +
    # mux-internal counters: no flow violations, no SYN sheds needed)
    assert not d["bound_violated"]
    assert d["queued_max"] <= cfg.max_queued
    assert d["running_max"] <= cfg.max_concurrent
    assert d["flow_violations"] == 0
    assert d["write_deadline_sheds"] == 0

    # the fleet really went through admission (control + job sessions)
    assert d["admission"]["admitted"] >= 2 * n_agents
    assert "admission_rejected" in d          # reported even when 0

    # mux throughput measured over real frames
    assert d["mux_frames_total"] > 10 * n_agents
    assert d["mux_frames_per_s"] > 0

    _assert_traced(rep, n_agents, d)
    return d


def test_fleet_soak_n100(tmp_path):
    d = _soak(tmp_path, 100)
    # the execution gate really bounds concurrency: with 8 slots the
    # whole fleet cannot run at once, so queueing must have been observed
    assert d["queued_max"] > 8


@pytest.mark.slow
def test_fleet_soak_n500(tmp_path):
    _soak(tmp_path, 500)


def test_fleet_soak_full_profile(tmp_path):
    """Opt-in N=500 run in the default loop (PBS_PLUS_FLEET=1)."""
    if not FULL:
        pytest.skip("set PBS_PLUS_FLEET=1 for the N=500 profile")
    _soak(tmp_path, 500)


def test_fleet_hostile_slow_reader_profile(tmp_path):
    """ISSUE 15 satellite: hostile agents drive the PR 7 mux paths a
    soak never exercised — the RX-credit reset (an agent floods DATA
    past its advertised credit → server counts a flow violation and
    resets the stream) and the write-deadline shed (an agent stops
    draining its socket while demanding echo payloads → the server's
    blocked write sheds the CONNECTION).  Both are counted server-side
    and every legit agent still publishes."""
    cfg = FleetConfig(n_agents=12, tenants=4, max_concurrent=4,
                      max_queued=64, hostile_agents=2,
                      mux_write_deadline_s=0.4)
    rep = run_fleet(str(tmp_path / "ds"), cfg)
    d = rep.to_dict()
    # survivors: the whole legit fleet published despite the abuse
    assert d["published"] == 12, rep.failures
    assert not rep.failures
    assert d["hostile_run"] == 2
    # every hostile tripped the RX-credit bound exactly once (stream
    # reset, bounded buffering) …
    assert d["server_flow_violations"] >= 2
    # … and at least one refused-drain connection was shed at the
    # write deadline (the kernel may coalesce the two floods' timing,
    # so ≥1 is the structural floor)
    assert d["server_write_deadline_sheds"] >= 1


def _mixed_cfg(n_agents: int, **kw) -> FleetConfig:
    """The ISSUE 19 survival composition: multi-wave backups with
    churn, restore/verify/sync lanes, weighted tenants, and all five
    hostile profiles in one run."""
    base = dict(
        n_agents=n_agents, tenants=8, max_concurrent=8,
        max_queued=4 * n_agents,
        jobs_per_agent=2, churn_fraction=0.1,
        restore_jobs=max(4, n_agents // 10),
        verify_jobs=max(4, n_agents // 10),
        sync_jobs=4,
        hostile_agents=5,
        hostile_profiles=("flood,slow_reader,reconnect_storm,"
                          "length_liar,slowloris"),
        # a 20s reservation TTL would stall the slowloris strand wait;
        # a 60s write deadline would stall the slow-reader shed
        reservation_ttl_s=1.0,
        mux_write_deadline_s=2.0,
        tenant_weights="tenant-0=3,tenant-1=2",
        # the mount-serve read lane (ISSUE 20) rides EVERY mixed run:
        # Zipf random-access readers through one shared sharded cache,
        # concurrent with the ingest still in flight
        readserve_readers=max(4, n_agents // 10),
        readserve_reads=6,
    )
    base.update(kw)
    return FleetConfig(**base)


def _mixed_assertions(cfg: FleetConfig, rep, d: dict) -> None:
    # every wave of every legit agent published; nothing failed
    assert d["published"] == cfg.n_agents * cfg.jobs_per_agent, \
        rep.failures
    assert not rep.failures
    # mixed-traffic lanes all completed through the same slots
    assert d["restore_completed"] == cfg.restore_jobs, \
        rep.restore_failures
    assert d["restore_failed"] == 0
    assert d["verify_completed"] == cfg.verify_jobs, rep.verify_failures
    assert d["verify_failed"] == 0
    # sync_jobs concurrent rounds plus the final catch-up pass
    assert d["sync_completed"] >= cfg.sync_jobs, rep.sync_failures
    assert d["sync_failed"] == 0
    # keepalive churn really dropped and redialed control transports
    assert d["churned"] >= 1
    # the mount-serve read lane completed every reader job with every
    # ranged read verified bit-for-bit, ingest published concurrently
    # (zero starvation both ways), and the shared sharded cache really
    # absorbed the Zipf mix (hits + probation promotions observed)
    assert d["readserve_completed"] == cfg.readserve_readers, \
        rep.readserve_failures
    assert d["readserve_failed"] == 0
    assert d["readserve_reads"] == \
        cfg.readserve_readers * cfg.readserve_reads
    assert d["readserve_bytes"] > 0
    assert d["readserve_cache"].get("hits", 0) > 0
    # all five hostile profiles ran and each left its server-side mark:
    # flood → RX-credit reset; slow_reader → write-deadline shed;
    # length_liar → typed StreamLengthError counted per-conn and the
    # liar's backup failing in ITS lane (never report.failures);
    # reconnect_storm → newest-wins evictions; slowloris → stranded
    # reservations reaped by the TTL sweeper
    assert d["hostile_run"] == cfg.hostile_agents
    assert d["server_flow_violations"] >= 1
    assert d["server_write_deadline_sheds"] >= 1
    assert d["server_stream_length_violations"] >= 1
    assert d["hostile_liar_errors"] >= 1
    assert d["hostile_liar_published"] == 0
    assert d["evictions"] >= 1
    assert d["reservations_reaped"] >= cfg.hostile_slowloris_rounds
    # weighted shares: the pinned tenants took part in contended grants
    # and NO tenant starved (every backup lane landed grants); the ±10%
    # proportionality property itself is test_fairness.py's job — a
    # live soak's backlogs come and go, so only starvation-freedom is a
    # stable assertion here
    for t in range(cfg.tenants):
        assert rep.tenant_grants.get(f"tenant-{t}", 0) > 0, \
            rep.tenant_grants
    # latency still measured and ordered under abuse
    assert 0 < d["enqueue_to_publish_p50_s"] <= d["enqueue_to_publish_p99_s"]
    # bounds held through the whole mixed run
    assert not d["bound_violated"]
    assert d["queued_max"] <= cfg.max_queued


# The mixed soak has been seen to stop for good with its event loop idle
# in select() and every pool thread parked (an await nothing wakes:
# ROADMAP D0).  There is no pytest-timeout wheel here, so the test
# carries its own limit: it FAILS after this long instead of holding its
# xdist worker — and the tests queued behind it — until the run is cut.
SOAK_LIMIT_S = 120.0


def _run_fleet_bounded(datastore_dir: str, cfg, limit_s: float):
    """``run_fleet`` on a daemon thread, joined with a time limit.  A
    soak that outlives the limit fails the test; its thread is left
    parked where it hung (idle, daemon) rather than waited for."""
    import threading
    box: dict = {}

    def work():
        try:
            box["rep"] = run_fleet(datastore_dir, cfg)
        except BaseException as e:     # re-raised on the test's thread
            box["exc"] = e

    t = threading.Thread(target=work, name="fleet-soak", daemon=True)
    t.start()
    t.join(limit_s)
    if t.is_alive():
        pytest.fail(f"fleet soak still running after {limit_s:.0f}s "
                    "(ROADMAP D0: lost wake-up)")
    if "exc" in box:
        raise box["exc"]
    return box["rep"]


def test_fleet_soak_mixed_traffic_hostiles(tmp_path):
    """ISSUE 19: the N=100 survival soak — two backup waves per agent
    with keepalive churn, restore + verify + sync lanes concurrent with
    the backups, weighted tenants, and all five hostile profiles
    (flood, slow_reader, reconnect_storm, length_liar, slowloris)
    attacking the same listener.  Every legit job publishes, every
    attack is observed server-side, every bound holds."""
    cfg = _mixed_cfg(100)
    rep = _run_fleet_bounded(str(tmp_path / "ds"), cfg, SOAK_LIMIT_S)
    _mixed_assertions(cfg, rep, rep.to_dict())


@pytest.mark.slow
def test_fleet_survival_n2000(tmp_path):
    """ISSUE 19 tentpole profile: N=2000 agents, two waves each (4000
    backups), churn, mixed traffic, and the full hostile composition —
    the scaled survival acceptance, opt-in via PBS_PLUS_FLEET=1 (see
    tools/verify_lint.sh)."""
    if not FULL:
        pytest.skip("set PBS_PLUS_FLEET=1 for the N=2000 profile")
    cfg = _mixed_cfg(2000, tenants=16, max_concurrent=16,
                     connect_concurrency=64, hostile_agents=10,
                     restore_jobs=40, verify_jobs=40, sync_jobs=8,
                     churn_fraction=0.05, job_timeout_s=900.0)
    rep = run_fleet(str(tmp_path / "ds"), cfg)
    _mixed_assertions(cfg, rep, rep.to_dict())


@pytest.mark.slow
def test_fleet_readserve_n_high(tmp_path):
    """ISSUE 20 scaled read-plane acceptance: hundreds of concurrent
    Zipf readers random-access two waves of published snapshots over a
    DELTA-TIER datastore through ONE sharded scan-resistant chunk
    cache, concurrent with the ingest — every ranged read verified
    bit-for-bit, zero starvation either way.  Opt-in via
    PBS_PLUS_FLEET=1 (tools/verify_lint.sh readserve leg)."""
    if not FULL:
        pytest.skip("set PBS_PLUS_FLEET=1 for the readserve profile")
    cfg = FleetConfig(n_agents=100, tenants=8, max_concurrent=16,
                      max_queued=4000, jobs_per_agent=2,
                      readserve_readers=300, readserve_reads=10,
                      delta_tier=True, job_timeout_s=900.0)
    rep = run_fleet(str(tmp_path / "ds"), cfg)
    d = rep.to_dict()
    # ingest published every wave despite 300 concurrent reader jobs
    assert d["published"] == 200, rep.failures
    assert not rep.failures
    # every reader completed with every byte verified
    assert d["readserve_completed"] == 300, rep.readserve_failures
    assert d["readserve_failed"] == 0
    assert d["readserve_reads"] == 3000
    # the shared cache absorbed the Zipf mix: the working set got
    # promoted out of probation and re-served from protected
    cc = d["readserve_cache"]
    assert cc["hits"] > 0
    assert cc["probation_promotions"] > 0
    assert cc["shards"] >= 2     # the 64 MiB lane cache really sharded


def test_fleet_open_rate_causes_typed_rejects(tmp_path):
    """With a tight global opens/s bucket the connect storm is throttled:
    agents observe 429 rejects, retry with backoff, and the WHOLE fleet
    still comes up — admission sheds load without losing it."""
    cfg = FleetConfig(n_agents=16, max_concurrent=8,
                      open_rate=10.0, connect_concurrency=16)
    rep = run_fleet(str(tmp_path / "ds"), cfg)
    d = rep.to_dict()
    assert d["published"] == 16
    # 32 session opens against a 10/s bucket (burst 20): some MUST have
    # been throttled, and the client-side retry counter must agree with
    # the server-side typed-reject counter
    assert d["admission"].get("open_rate", 0) > 0
    assert d["connect_rejects_seen_by_agents"] == \
        d["admission"]["open_rate"]


def test_fleet_session_ceiling_rejects_overflow(tmp_path):
    """max_sessions is a hard ceiling: a fleet bigger than the ceiling
    sees typed 503 rejects (AdmissionRejected kind=session_limit) and
    only ceiling-many control sessions register."""
    from pbs_plus_tpu.server.fleetsim import FleetServer, SimAgent, \
        synthetic_tree

    import asyncio

    async def main():
        cfg = FleetConfig(n_agents=8, max_sessions=5)
        server = FleetServer(str(tmp_path / "ds"), cfg)
        port = await server.start()
        agents = [SimAgent(f"sim-{i:04d}", "127.0.0.1", port,
                           synthetic_tree(1, i, 1, 1024),
                           connect_attempts=1)
                  for i in range(8)]
        ok = rejected = 0
        for a in agents:
            try:
                await a.start()
                ok += 1
            except ConnectionError:
                rejected += 1
        assert ok == 5 and rejected == 3
        stats = server.agents.admission_stats()
        assert stats["session_limit"] == 3
        for a in agents:
            await a.stop()
        await server.stop()

    asyncio.run(main())
