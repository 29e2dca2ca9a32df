#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no child that imports jax.  Set-up builds the seeded
backlog, starts the real server and agents, preloads the dedup index and
warms every program the window will use; the window drains the backlog in
a closed loop; afterwards what the window's jobs published is compared
with the plain reference (``harness/reference.py``).  Earlier lines of
standard output carry counts; the last line is the contract's one JSON
object.  Without a TPU, or with fewer chips than the cell asks for, it
says why and exits non-zero before any set-up, with no result line.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# A traced run traces a slice at the window's start: TRACE_SECONDS, or
# less when a hash dispatch comes sooner.  SHA-256's loop writes ~13,000
# trace events per millisecond of device time: one dispatch fills the
# profiler's buffer (6.3 M events) and the profiler then takes 780 s to
# stop (my chip runs, PR 23), where a run has 360 s.  So the trace, and
# ``busy_s`` and every metric read from it, describe the candidate scans
# and never the hash program; what the hash path costs over the whole
# window is read from the host's clock around the feeder's dispatches.
TRACE_SECONDS = 5.0
EXIT_NO_CHIP = 2
EXIT_COMPILED_IN_WINDOW = 3


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def metrics_of(cell_name: str, section: str) -> list[dict]:
    """The cell's metrics of one section: those that list the cell under
    ``workloads``, and those that list nothing."""
    return [m for m in manifest()[section]
            if cell_name in m.get("workloads", [cell_name])]


def look_for_chips(chips: int):
    """The accelerator, or the reason there is none.  Before any set-up."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, (f"jax found no TPU (platform "
                      f"{devices[0].platform!r}, JAX_PLATFORMS="
                      f"{os.environ.get('JAX_PLATFORMS', '')!r}); the "
                      "benchmark measures the chip and does not fall back")
    if len(devices) < chips:
        return None, f"the cell asks for {chips} chip(s), jax sees " \
                     f"{len(devices)}"
    return devices, ""


def configure_cache() -> str:
    """The persistent compilation cache, where the program's own rule puts
    it, with every program admitted — the sub-second ones too — so that
    only a checkout's first run compiles."""
    from pbs_plus_tpu.utils import jaxenv
    cache_dir = jaxenv.configure_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def device_report(devices) -> dict:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


# -- traced runs: the harness's own annotations, clocks and byte count --------

class FeedCounter:
    """Bytes the streams carried into ``TpuChunker.feed``, counted by the
    benchmark at the call: the work ``scan_roofline`` is taken against,
    whichever kernel (or none) then does it."""

    def __init__(self):
        self.bytes = 0
        self._lock = threading.Lock()

    def add(self, n: int) -> None:
        with self._lock:
            self.bytes += n


HOOKS = (("feeder", "_dispatch_masks", "bench.feeder.dispatch_masks"),
         ("feeder", "_dispatch_sha", "bench.feeder.dispatch_sha"),
         ("chunker", "feed", "bench.chunker.feed"))


def install_trace_wrappers(counter: FeedCounter, before_sha,
                           clock=time.monotonic):
    """Host annotations around the feeder's two dispatch paths and the
    chunker's feed, for attributing device-idle gaps, with the host's
    clock around each dispatch; ``before_sha`` runs on the feeder's thread
    before every hash dispatch.  A method the program no longer has is an
    error: a traced run without the hook on the hash dispatch would trace
    SHA-256 and never end.  Returns the lists of (start, end) by label
    and the function that takes the wrappers off."""
    from jax.profiler import TraceAnnotation
    from pbs_plus_tpu.models.dedup import TpuChunker
    from pbs_plus_tpu.models.feeder import DeviceFeeder
    classes = {"feeder": DeviceFeeder, "chunker": TpuChunker}
    before = {"_dispatch_sha": lambda reqs: before_sha(),
              "feed": lambda data: counter.add(len(data))}
    missing = [f"{classes[c].__name__}.{attr}" for c, attr, _ in HOOKS
               if not callable(getattr(classes[c], attr, None))]
    if missing:
        raise RuntimeError(f"the program has no {', '.join(missing)}: the "
                           "traced run's hooks need a new home")
    saved, spans = [], {}

    def wrap(cls, attr: str, label: str) -> None:
        inner, first, took = getattr(cls, attr), before.get(attr), []
        if cls is DeviceFeeder:
            spans[label] = took

        def outer(self, arg, *a, **kw):
            if first is not None:
                first(arg)
            t = clock()
            try:
                with TraceAnnotation(label):
                    return inner(self, arg, *a, **kw)
            finally:
                took.append((t, clock()))
        saved.append((cls, attr, inner))
        setattr(cls, attr, outer)

    for c, attr, label in HOOKS:
        wrap(classes[c], attr, label)

    def uninstall() -> None:
        for cls, attr, inner in saved:
            setattr(cls, attr, inner)
    return spans, uninstall


class DeviceTrace:
    """A ``jax.profiler`` trace of a slice at the window's start.  A
    thread of its own stops the profiler — after ``seconds``, or as soon
    as ``hold`` is called — never the caller's."""

    def __init__(self, directory: str, counter: FeedCounter,
                 seconds: float):
        self.directory = directory
        self.counter = counter
        self.seconds = seconds
        self.window_s = 0.0
        self.fed_bytes = 0
        self.stop_s = 0.0               # how long the profiler took to stop
        self.cut_short = False          # a hash dispatch came before the timer
        self.held_s = 0.0               # hash dispatches held while it stopped
        self._t0 = 0.0
        self._fed0 = 0
        self._ask = threading.Event()
        self._stopped = threading.Event()
        self._thread = None

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0       # the wrappers' annotations
        options.host_tracer_level = 2         # are enough of the host
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._t0, self._fed0 = time.monotonic(), self.counter.bytes
        self._thread = threading.Thread(target=self._stop_when_asked,
                                        name="bench-trace-stop", daemon=True)
        self._thread.start()

    def _stop_when_asked(self) -> None:
        import jax
        self.cut_short = self._ask.wait(self.seconds)
        self._ask.set()
        self.window_s = time.monotonic() - self._t0
        self.fed_bytes = self.counter.bytes - self._fed0
        try:
            jax.profiler.stop_trace()
        finally:
            self.stop_s = time.monotonic() - self._t0 - self.window_s
            self._stopped.set()

    def hold(self) -> None:
        """On the feeder's thread, before a hash dispatch: if the trace
        still runs, ask for its stop and wait here until it has stopped,
        so the hash program is never traced."""
        if self._thread is None or self._stopped.is_set():
            return
        self._ask.set()
        t = time.monotonic()
        self._stopped.wait()
        self.held_s += time.monotonic() - t

    def finish(self) -> None:
        if self._thread is not None:
            self._ask.set()
            self._thread.join()


# -- one run -------------------------------------------------------------------

async def restore_one(dep, job, dest: str) -> int:
    """One snapshot through the program's restore job, byte for byte
    against its tree: 0 when identical."""
    from benchmark.harness.reference import same_tree
    from pbs_plus_tpu.server import database
    from pbs_plus_tpu.server.restore_job import run_restore_job
    rid = f"bench-restore-{job.job_id}"
    dep.server.db.create_restore(rid, job.agent, job.snapshot, dest)
    await run_restore_job(dep.server, rid, target=job.agent,
                          snapshot=job.snapshot, destination=dest)
    agent = dep.agents[job.agent][0]
    for _ in range(1200):           # the agent's restore task writes on
        if not agent.jobs:
            break
        await asyncio.sleep(0.1)
    status = dep.server.db.get_restore(rid)["status"]
    ok = status == database.STATUS_SUCCESS and same_tree(dest,
                                                         job.tree_path)
    return 0 if ok else 1


async def run_cell(cell, *, seed: int, seconds: float, trace: bool,
                   work: str, devices, t_process: float = T_PROCESS,
                   trace_seconds: float = TRACE_SECONDS,
                   controls: dict | None = None) -> dict | None:
    """Set-up, window, comparison.  Returns the result object, or None
    when a program was compiled inside the window (the run is void).
    ``controls`` (name -> a function from a stream to its cuts) are put
    in the reference's place one by one; what each reads is returned
    under ``controls`` (``benchmark/control.py``, never a measured run)."""
    import numpy as np

    from benchmark.harness import loadgen, reference, tracereduce, window
    from pbs_plus_tpu.utils import trace as ptrace

    cfg, loop = cell.config, asyncio.get_running_loop()
    compiles = loadgen.Compiles()
    peaks = tracereduce.load_peaks()
    if trace:       # an unknown device kind is an error, and an early one
        tracereduce.peak_bytes_per_s(peaks, devices[0].device_kind)

    # -- set-up ---------------------------------------------------------------
    marks = {"start": time.monotonic()}
    trees = loadgen.build_trees(os.path.join(work, "trees"), seed, cfg)
    warm = loadgen.build_trees(os.path.join(work, "trees"), seed, cfg,
                               warm=True) if cfg.get("warm_tree") else {}
    marks["trees"] = time.monotonic()
    dep = loadgen.Deployment(os.path.join(work, "srv"), cfg)
    counter = FeedCounter()
    commits = loadgen.CommitLog()
    dtrace = DeviceTrace(os.path.join(work, "trace"), counter,
                         min(trace_seconds, seconds)) if trace else None
    uninstall, dispatch_spans = None, {}
    sha_spans: list = []

    def on_span(rec: dict) -> None:
        if rec["name"] == "ingest.sha" and \
                not (rec.get("attrs") or {}).get("aggregated"):
            sha_spans.append((rec["start"], rec["dur_s"]))
    try:
        await dep.start(sorted(trees))
        commits.watch(dep.chunks)
        dep.register(trees)
        dep.register(warm)
        marks["server"] = time.monotonic()
        preloaded = await loop.run_in_executor(
            None, dep.preload_index, seed,
            cfg.get("index_preload_digests", 0))
        marks["preload"] = time.monotonic()
        shapes = await loop.run_in_executor(None, loadgen.warm_shapes, cfg)
        marks["shapes"] = time.monotonic()
        backlog = loadgen.plan_backlog(trees, cell.traffic)
        warm_run = await loadgen.drain_backlog(
            dep.server, loadgen.plan_backlog(warm, cell.traffic),
            seconds=3600.0) if warm else None
        bad = [j for j in (warm_run.jobs if warm_run else [])
               if j.status != "success"]
        if bad:
            raise RuntimeError(f"warm-up backup failed: {bad[0].job_id}: "
                               f"{bad[0].status}: {bad[0].error}")
        marks["warm"] = time.monotonic()
        if trace:
            dispatch_spans, uninstall = install_trace_wrappers(
                counter, dtrace.hold)
            ptrace.subscribe(on_span)
        gc.collect()
        before = loadgen.device_counters()
        c_before = compiles.snapshot()
        fed0 = counter.bytes
        if dtrace:
            dtrace.start()
        setup_s = time.monotonic() - t_process
        wall0 = time.time()

        # -- the window -----------------------------------------------------------
        at_end: dict = {}

        def on_end() -> None:
            at_end["counters"] = loadgen.device_counters()
            at_end["compiles"] = compiles.snapshot()
            at_end["wall"] = time.time()
            at_end["fed"] = counter.bytes
        run = await loadgen.drain_backlog(dep.server, backlog, seconds,
                                          on_end=on_end)
        if dtrace:
            await loop.run_in_executor(None, dtrace.finish)
        device = device_report(devices)
        in_window = at_end["compiles"]["compilations"] \
            - c_before["compilations"]
        interval = window.interval_seconds(run.t0, run.t_end)
        published_in = window.published_inside(run.jobs, run.t0, run.t_end)
        counters = loadgen.counter_deltas(before, at_end["counters"])
        say(phase="window", interval_s=round(interval, 3),
            drained=run.drained, jobs_enqueued=len(run.jobs),
            jobs_published_in_window=published_in,
            setup_s=round(setup_s, 3),
            setup_parts={k: round(marks[k] - marks[p], 3) for p, k in
                         zip(list(marks), list(marks)[1:])},
            index_preloaded=preloaded, shapes_warmed=shapes,
            compiles_setup=c_before,
            slowest_programs_s=sorted(compiles.durations)[-6:],
            compiles_in_window=in_window, counters=counters)
        if run.drained:
            say(note="the backlog drained before the window ended: the "
                     "interval ends at the last publish; the cell needs "
                     "resizing")
        if in_window:
            print(f"benchmark: {in_window} program(s) were compiled inside "
                  "the measured window; the run is void", file=sys.stderr)
            return None

        # -- what the window's jobs published, against the reference -------------
        t_cmp = time.monotonic()
        ok_jobs = [j for j in run.jobs if j.status == "success"]
        failed = len(run.jobs) - len(ok_jobs)
        rng = np.random.default_rng([seed, 3])
        restore_mismatch = 1
        if ok_jobs:
            pick = ok_jobs[int(rng.integers(len(ok_jobs)))]
            restore_mismatch = await restore_one(
                dep, pick, os.path.join(work, "restored"))

        def compare() -> dict:
            known_before = set()
            for j in (warm_run.jobs if warm_run else []):
                for _, digests, _ in reference.read_published(
                        dep.server, j).streams.values():
                    known_before.update(digests)
            published = [reference.read_published(dep.server, j)
                         for j in ok_jobs]
            # the rate's numerator, now that the chunks' lengths are known;
            # and the count's own check: from t0 to the last publish the
            # store took exactly the bytes the jobs published
            sizes, published_bytes = loadgen.chunk_sizes(
                s for p in published for s in p.streams.values())
            in_interval, _ = commits.bytes_between(run.t0, run.t_end,
                                                   sizes)
            in_all, unknown = commits.bytes_between(run.t0, run.t_drained,
                                                    sizes)
            common = dict(
                uncounted_commit_bytes=abs(in_all - published_bytes)
                + unknown,
                known_before=known_before,
                avgs={"payload": cfg["server"]["chunk_avg"],
                      "meta": cfg["meta_chunk_avg"]},
                jobs_not_published=failed,
                restore_mismatch=restore_mismatch)
            verdict = reference.compare(published, **common)
            verdict["controls"] = {
                name: reference.compare(published, cuts_of=cuts, **common)
                for name, cuts in (controls or {}).items()}
            verdict["committed"] = (in_interval, in_all, published_bytes)
            return verdict
        verdict = await loop.run_in_executor(None, compare)
        committed, committed_all, published_bytes = verdict["committed"]
        rate = window.ingest_mib_s(committed, run.t0, run.t_end)
        say(phase="compare", seconds=round(time.monotonic() - t_cmp, 3),
            bytes_committed_in_window=committed,
            bytes_committed_to_last_publish=committed_all,
            bytes_published=published_bytes, ingest_mib_s=rate,
            jobs_from_t0_s=[[j.job_id, round(j.enqueued - run.t0, 2),
                             round(j.done - run.t0, 2)] for j in run.jobs],
            **verdict["seen"])
    finally:
        ptrace.unsubscribe(on_span)
        if uninstall is not None:
            uninstall()
        if dtrace is not None:
            dtrace.finish()
        await dep.stop()

    # -- metrics ---------------------------------------------------------------
    win = window.Window(
        seconds=interval, loop=run, counters=counters,
        sha_spans=[d for s, d in sha_spans if wall0 <= s <= at_end["wall"]],
        fed_bytes=at_end["fed"] - fed0,
        dispatch_s={label: window.seconds_inside(took, run.t0, run.t_end)
                    for label, took in dispatch_spans.items()},
        device_kind=device["kind"])
    result = {"correct": verdict["correct"], "attempted": len(run.jobs),
              "failed": failed}
    if trace:
        t_load = time.monotonic()
        xplane = tracereduce.find_xplane(dtrace.directory)
        events = tracereduce.load_events(xplane)
        say(phase="trace", traced_s=round(dtrace.window_s, 3),
            cut_short_by_hash_dispatch=dtrace.cut_short,
            hash_dispatch_held_s=round(dtrace.held_s, 3),
            stop_trace_s=round(dtrace.stop_s, 3),
            xplane_bytes=os.path.getsize(xplane),
            load_events_s=round(time.monotonic() - t_load, 3),
            device_events={p: {ln: len(evs) for ln, evs in lines.items()}
                           for p, lines in events["devices"].items()},
            host_events=len(events["host"]))
        win.trace = tracereduce.reduce(
            events, window_s=dtrace.window_s, fed_bytes=dtrace.fed_bytes,
            device_kind=device["kind"], peaks=peaks)
        dump = os.environ.get("BENCH_DUMP_EVENTS")
        if dump:
            with open(dump, "w", encoding="utf-8") as f:
                json.dump(events, f)
        metrics = {}
        for m in metrics_of(cell.name, "per_layer"):
            value = window.read_metric(m["name"], win)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = win.trace["busy_s"]
        device["window_s"] = win.trace["window_s"]
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": win.trace.get("device_ops", []),
            "idle_gaps": win.trace.get("idle_gaps", [])})
    else:
        units = {m["name"]: m["unit"]
                 for m in metrics_of(cell.name, "end_to_end")}
        values = {"setup_s": setup_s, "ingest_mib_s": rate}
        result.update(metrics={k: {"value": values[k], "unit": units[k]}
                               for k in units}, device=device)
    if controls:
        result["controls"] = {
            name: {"correct": v["correct"], "compared": v["compared"]}
            for name, v in verdict["controls"].items()}
    result["compared"] = verdict["compared"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import loadgen
    cell = loadgen.load_cell(args.workload)
    devices, why = look_for_chips(cell.chips)
    if devices is None:
        print(f"benchmark: {why}", file=sys.stderr)
        return EXIT_NO_CHIP
    devices = devices[:cell.chips]

    cache_dir = configure_cache()
    work = tempfile.mkdtemp(prefix="bench-")
    say(phase="start", workload=cell.name, seed=args.seed,
        seconds=args.seconds, trace=args.trace,
        device_kind=devices[0].device_kind, devices=len(devices),
        compile_cache=cache_dir)
    try:
        result = asyncio.run(run_cell(
            cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), work=work, devices=devices))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    if result is None:
        return EXIT_COMPILED_IN_WINDOW
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
