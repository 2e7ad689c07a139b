"""Fleet-wide telemetry: registry, event log, exposition, traces (ISSUE 9).

The contract under test:

- the :mod:`repro.obs` primitives themselves — idempotent instrument
  getters, label canonicalization, histogram bucketing, Prometheus text
  rendering, event-log schema/generation/torn-tail semantics, Chrome
  trace_event conversion;
- **zero perturbation**: turning every telemetry knob on (registry, event
  log, a profiler trace with its spans) leaves trajectories bitwise
  identical — pinned
  both A/B (service_tuner with vs without telemetry) and against the
  committed golden fixture (``server_two_jobs`` replayed on a fully
  instrumented server);
- the wire surface: the read-only ``metrics`` verb ships a registry
  snapshot that renders to Prometheus text client-side, and ``status``
  carries the pool's ``retried``/``abandoned`` and each job's
  ``memo_hits``;
- crash-safe generations: a true SIGKILL of ``soc-service serve --events``
  leaves a log whose resume run appends a NEW generation; within each
  generation the scheduler's ``counters`` instants never regress, and the
  whole log renders to a valid non-empty Chrome trace.
"""
import importlib.util
import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from repro.obs import (EventLog, MetricsRegistry, build_chrome_trace,
                       log_progress, read_events, records,
                       render_prometheus, summarize_events)
from repro.obs.metrics import DEFAULT_BUCKETS, parse_label_key
from repro.service import JobSpec, TunerServer, request, service_tuner
from repro.soc import VLSIFlow

from test_server import KW, TRANSF, _cli_env, _serve_in_thread, _strip_wall

GOLDEN = os.path.join(os.path.dirname(__file__), "golden")


@pytest.fixture(scope="module")
def pool96(space):
    return np.asarray(space.sample(jax.random.PRNGKey(7), 96))


# --------------------------------------------------------------- registry
def test_registry_idempotent_getters_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("x_total", "help text")
    assert reg.counter("x_total") is c  # idempotent: same instrument
    g = reg.gauge("depth")
    assert reg.gauge("depth") is g
    h = reg.histogram("lat_seconds")
    assert reg.histogram("lat_seconds") is h
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="already registered as gauge"):
        reg.histogram("depth")


def test_counter_gauge_semantics_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("ops_total")
    c.inc()
    c.inc(2, stage="fit")
    c.inc(3, stage="fit")
    assert c.value() == 1.0
    assert c.value(stage="fit") == 5.0
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)
    g = reg.gauge("level")
    g.set(7.0)
    g.dec(2.5)
    assert g.value() == 4.5
    snap = reg.snapshot()
    assert snap["counters"]["ops_total"]["series"] == {"": 1.0,
                                                       "stage=fit": 5.0}
    assert snap["gauges"]["level"]["series"] == {"": 4.5}


def test_label_key_is_canonical_and_rejects_reserved_chars():
    c = MetricsRegistry().counter("c_total")
    c.inc(1, b="2", a="1")
    c.inc(1, a="1", b="2")  # keyword order must not matter
    assert c.value(a="1", b="2") == 2.0
    assert parse_label_key("a=1,b=2") == {"a": "1", "b": "2"}
    assert parse_label_key("") == {}
    for bad in ("x,y", "x=y", 'x"y', "x\ny"):
        with pytest.raises(ValueError, match="reserved"):
            c.inc(1, lab=bad)


def test_histogram_buckets_and_overflow():
    h = MetricsRegistry().histogram("h", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    (series,) = h._snapshot().values()
    assert series["counts"] == [1, 2, 1, 1]  # last = +Inf overflow
    assert series["count"] == 5
    assert series["sum"] == pytest.approx(56.05)
    with pytest.raises(ValueError, match="bucket"):
        MetricsRegistry().histogram("empty", buckets=())


def test_collectors_run_at_snapshot_and_swallow_errors():
    reg = MetricsRegistry()
    live = {"hits": 3}
    g = reg.gauge("cache_hits")
    reg.add_collector(lambda: g.set(live["hits"]))
    reg.add_collector(lambda: 1 / 0)  # dead component: must not break scrape
    assert reg.snapshot()["gauges"]["cache_hits"]["series"] == {"": 3.0}
    live["hits"] = 9
    assert reg.snapshot()["gauges"]["cache_hits"]["series"] == {"": 9.0}


def test_prometheus_rendering_roundtrips_the_snapshot():
    reg = MetricsRegistry()
    reg.counter("jobs_total", "jobs seen").inc(4, state="DONE")
    reg.gauge("depth").set(2)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.5, 2.0))
    h.observe(0.1, src="worker")
    h.observe(1.0, src="worker")
    h.observe(9.0, src="worker")
    text = render_prometheus(reg.snapshot())
    assert text == reg.to_prometheus()
    lines = text.splitlines()
    assert "# HELP jobs_total jobs seen" in lines
    assert "# TYPE jobs_total counter" in lines
    assert 'jobs_total{state="DONE"} 4.0' in lines
    assert "# TYPE depth gauge" in lines
    assert "depth 2.0" in lines
    # histogram: cumulative le buckets + implicit +Inf, sum and count
    assert 'lat_seconds_bucket{src="worker",le="0.5"} 1' in lines
    assert 'lat_seconds_bucket{src="worker",le="2.0"} 2' in lines
    assert 'lat_seconds_bucket{src="worker",le="+Inf"} 3' in lines
    assert 'lat_seconds_sum{src="worker"} 10.1' in lines
    assert 'lat_seconds_count{src="worker"} 3' in lines
    assert text.endswith("\n")
    assert render_prometheus({"counters": {}, "gauges": {},
                              "histograms": {}}) == ""


def test_default_buckets_are_sorted():
    assert list(DEFAULT_BUCKETS) == sorted(DEFAULT_BUCKETS)


# -------------------------------------------------------------- event log
def test_event_log_schema_roundtrip(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with EventLog(path, run="unit") as ev:
        ev.instant("tick", cat="test", track="t0", n=1,
                   skipped=None, npval=np.float32(0.5))
        ev.begin("work", track="t0")
        ev.end("work", track="t0", done=True)
        with pytest.raises(RuntimeError):
            with ev.span("boom", track="t0"):
                raise RuntimeError("x")
    recs = read_events(path)
    assert [r["kind"] for r in recs] == ["M", "I", "B", "E", "B", "E"]
    assert recs[0]["run"] == "unit" and recs[0]["pid"] == os.getpid()
    assert all(r["gen"] == 0 for r in recs)
    monos = [r["mono"] for r in recs]
    assert monos == sorted(monos)  # monotonic within a generation
    tick = recs[1]
    assert tick["name"] == "tick" and tick["cat"] == "test"
    assert tick["track"] == "t0" and tick["n"] == 1
    assert "skipped" not in tick  # None fields are dropped
    assert tick["npval"] == 0.5 and isinstance(tick["npval"], float)
    assert recs[5]["name"] == "boom" and recs[5]["error"] is True
    ev.instant("after-close")  # silently ignored, never raises
    assert len(read_events(path)) == 6


def test_event_log_generations_survive_reopen(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    for expect in (0, 1, 2):
        with EventLog(path, run=f"run{expect}") as ev:
            assert ev.generation == expect
            ev.instant("cycle", cycle=expect)
        assert (tmp_path / "ev.jsonl.gen").read_text() == str(expect)
    gens = [r["gen"] for r in read_events(path)]
    assert gens == [0, 0, 1, 1, 2, 2]


def test_read_events_tolerates_torn_tail_only(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with EventLog(path) as ev:
        ev.instant("a")
        ev.instant("b")
    with open(path, "a") as f:
        f.write('{"gen":0,"kind":"I","mono":1.0,"name":"to')  # SIGKILL tear
    recs = read_events(path)
    assert [r.get("name") for r in recs] == ["generation", "a", "b"]
    with open(path, "a") as f:  # tear now mid-file -> real corruption
        f.write('\n{"gen":0,"kind":"I","mono":2.0,"name":"c"}\n')
    with pytest.raises(json.JSONDecodeError):
        read_events(path)


# ----------------------------------------------------------- chrome trace
def _rec(gen, kind, mono, name, **kw):
    return {"gen": gen, "kind": kind, "mono": mono, "name": name, **kw}


def test_build_chrome_trace_spans_instants_and_flow_pairs():
    recs = [
        _rec(0, "M", 10.0, "generation", run="t"),
        _rec(0, "B", 10.0, "cycle", track="scheduler", cat="sched"),
        _rec(0, "I", 10.1, "pool.submit", track="pool", ticket=7, row=3),
        _rec(0, "I", 10.4, "pool.complete", track="pool", ticket=7),
        _rec(0, "I", 10.5, "counters", track="scheduler", cycles=1),
        _rec(0, "E", 10.6, "cycle", track="scheduler", cat="sched"),
    ]
    trace = build_chrome_trace(recs)
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    assert all(e["ts"] >= 0 for e in evs if "ts" in e)
    (x,) = [e for e in evs if e["ph"] == "X"]
    assert x["name"] == "cycle" and x["dur"] == pytest.approx(0.6e6)
    (b,) = [e for e in evs if e["ph"] == "b"]
    (e,) = [e for e in evs if e["ph"] == "e"]
    assert b["id"] == e["id"] == 7 and b["scope"] == "flow"
    assert b["args"]["row"] == 3
    names = {ev["args"]["name"] for ev in evs
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert {"scheduler", "pool"} <= names


def test_build_chrome_trace_rebases_generations_and_closes_crash_spans():
    recs = [
        _rec(0, "B", 100.0, "step", track="j0"),   # never ended: crash
        _rec(0, "I", 101.0, "tick", track="j0"),
        _rec(1, "I", 5.0, "tick", track="j0"),     # clock restarted
        _rec(1, "I", 6.0, "tick", track="j0"),
    ]
    evs = build_chrome_trace(recs)["traceEvents"]
    (x,) = [e for e in evs if e["ph"] == "X"]
    assert x["args"]["unterminated"] is True
    assert x["dur"] == pytest.approx(1e6)  # closed at its generation's end
    t0 = [e["ts"] for e in evs if e["ph"] == "i" and e["pid"] == 0]
    t1 = [e["ts"] for e in evs if e["ph"] == "i" and e["pid"] == 1]
    assert max(t0) < min(t1)  # generations are disjoint on the timeline
    assert min(t0) >= 0 and min(t1) >= 0


def test_summarize_events_counts_spans_and_instants():
    recs = [
        _rec(0, "M", 1.0, "generation", run="svc", wall=123.0),
        _rec(0, "B", 1.0, "cycle", track="scheduler"),
        _rec(0, "E", 3.0, "cycle", track="scheduler"),
        _rec(0, "I", 3.5, "counters", track="scheduler"),
        _rec(1, "I", 0.5, "counters", track="scheduler"),
    ]
    s = summarize_events(recs)
    assert s["generations"][0]["run"] == "svc"
    assert s["generations"][0]["records"] == 4
    assert s["generations"][0]["duration_s"] == pytest.approx(2.5)
    assert s["generations"][1]["records"] == 1
    sched = s["tracks"]["scheduler"]
    assert sched["spans"]["cycle"] == {"count": 1,
                                       "total_s": pytest.approx(2.0)}
    assert sched["instants"]["counters"] == 2


# --------------------------------------------------------- progress helper
def test_log_progress_format_and_event(tmp_path, capsys):
    history = []
    y = np.array([[1.0, 2.0, 3.0], [0.5, 2.5, 3.5]])  # mutually nondominated
    path = str(tmp_path / "ev.jsonl")
    with EventLog(path) as ev:
        rec = log_progress(history, y, 2, 0, None, verbose=True,
                           tag="soc-tuner", events=ev, track="t")
        log_progress(history, y, 3, 1, None, verbose=False, tag="service",
                     label="resnet50", word="eval", events=ev, track="t",
                     cycle=4)
    assert history == [rec, history[1]]  # records appended in order
    out = capsys.readouterr().out
    assert out == "[soc-tuner] round   0 evals=   2 front=  2\n"
    recs = [r for r in read_events(path) if r["kind"] == "I"]
    assert len(recs) == 2  # verbose=False still emitted the event record
    assert recs[0]["name"] == "round" and recs[0]["evaluations"] == 2
    assert recs[0]["track"] == "t" and recs[0]["pareto_size"] == 2
    assert recs[1]["cycle"] == 4 and recs[1]["round"] == 1


# -------------------------------------------------- zero perturbation (A/B)
def test_service_tuner_trajectory_identical_with_telemetry_on(
        tmp_path, space, small_pool):
    kw = dict(T=4, n=10, b=6, gp_steps=25, q=2, min_done=1,
              key=jax.random.PRNGKey(3), executor="inline")
    ref = service_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                        **kw)
    reg = MetricsRegistry()
    with jax.profiler.trace(str(tmp_path / "trace")):
        n0 = len(records())
        obs = service_tuner(space, small_pool, VLSIFlow(space, "resnet50"),
                            metrics=reg, events=str(tmp_path / "ev.jsonl"),
                            **kw)
        spans = records()[n0:]
    np.testing.assert_array_equal(ref.evaluated_rows, obs.evaluated_rows)
    np.testing.assert_array_equal(ref.y, obs.y)
    assert _strip_wall(ref.history) == _strip_wall(obs.history)
    # and the instrumentation actually recorded the run:
    snap = reg.snapshot()
    assert snap["counters"]["pool_completed_total"]["series"][""] >= kw["T"]
    assert snap["counters"]["engine_rounds_total"]["series"][""] > 0
    names = {r.name for r in spans}
    assert names >= {"tuner.prologue", "tuner.flow", "tuner.record",
                     "engine.select", "engine.pack", "engine.launch",
                     "engine.fetch", "engine.fantasy", "engine.observe"}
    assert all(r.t1 is not None and not r.failed for r in spans)
    recs = read_events(str(tmp_path / "ev.jsonl"))
    assert {r["name"] for r in recs} >= {"pool.submit", "pool.complete",
                                         "round"}


def test_golden_server_fixture_replays_with_full_telemetry(tmp_path, space):
    """The committed ``server_two_jobs`` fixture replayed on a server with
    every telemetry knob on: the pinned pick sequences must be untouched
    (golden parity), and the registry/event log must describe the run."""
    spec = importlib.util.spec_from_file_location(
        "regen_golden_obs", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "regen_golden.py"))
    rg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rg)
    with open(os.path.join(GOLDEN, "server_two_jobs.json")) as f:
        pinned = json.load(f)
    pool = np.asarray(space.sample(jax.random.PRNGKey(rg.POOL_SEED),
                                   rg.N_POOL))
    reg = MetricsRegistry()
    ev_path = str(tmp_path / "server.jsonl")
    with TunerServer(space, pool, executor="inline",
                     cache_dir=str(tmp_path / "fc"),
                     metrics=reg, events=ev_path) as srv:
        jids = []
        for wl, seed, extra in pinned["config"]["jobs"]:
            jids.append(srv.submit(
                JobSpec(workload=wl, seed=seed, **extra, **rg.RUN_KW),
                reference_front=rg._reference_front(space, pool, wl)))
        srv.run_until_idle()
        for jid in jids:
            job = srv.job(jid)
            assert job.status == "DONE", (jid, job.error)
            want = pinned["trajectories"][job.label]
            assert [int(r) for r in job.result().evaluated_rows] == \
                want["evaluated_rows"], (
                f"{job.label}: trajectory perturbed by telemetry")
            assert float(job.result().history[-1]["adrs"]) == \
                pytest.approx(want["final_adrs"], rel=1e-6)
        snap = reg.snapshot()
    trans = snap["counters"]["job_transitions_total"]["series"]
    assert trans["from=PENDING,to=RUNNING"] == len(jids)
    assert trans["from=RUNNING,to=DONE"] == len(jids)
    assert snap["counters"]["scheduler_cycles_total"]["series"][""] == \
        srv.cycles
    assert snap["gauges"]["server_jobs"]["series"]["state=DONE"] == \
        len(jids)
    assert snap["gauges"]["flow_disk_puts"]["series"][""] > 0
    hist = snap["histograms"]["scheduler_cycle_seconds"]["series"][""]
    assert hist["count"] == srv.cycles
    s = summarize_events(ev_path)
    assert s["tracks"]["scheduler"]["spans"]["cycle"]["count"] == srv.cycles
    assert set(jids) <= set(s["tracks"])  # every job has its own track


def test_golden_tuner_fixtures_replay_with_telemetry(tmp_path, space):
    """The remaining golden fixtures with telemetry on: the instrumented
    single-scenario driver (``service_tuner`` q=1 inline ≡ ``soc_tuner``
    incremental, pinned by test_service) must land on the
    ``soc_tuner_incremental`` pick sequence, and the instrumented fleet
    driver on ``fleet_tuner_incremental``'s."""
    from repro.core import FleetScenario
    from repro.service import fleet_service

    spec = importlib.util.spec_from_file_location(
        "regen_golden_obs2", os.path.join(os.path.dirname(__file__), "..",
                                          "tools", "regen_golden.py"))
    rg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rg)
    pool = np.asarray(space.sample(jax.random.PRNGKey(rg.POOL_SEED),
                                   rg.N_POOL))

    with open(os.path.join(GOLDEN, "soc_tuner_incremental.json")) as f:
        pinned = json.load(f)["trajectories"]["resnet50"]
    res = service_tuner(space, pool, VLSIFlow(space, "resnet50"),
                        key=jax.random.PRNGKey(3), q=1, executor="inline",
                        reference_front=rg._reference_front(space, pool,
                                                            "resnet50"),
                        metrics=MetricsRegistry(),
                        events=str(tmp_path / "soc.jsonl"), **rg.RUN_KW)
    assert [int(r) for r in res.evaluated_rows] == pinned["evaluated_rows"]
    assert float(res.history[-1]["adrs"]) == \
        pytest.approx(pinned["final_adrs"], rel=1e-6)

    with open(os.path.join(GOLDEN, "fleet_tuner_incremental.json")) as f:
        pinned = json.load(f)["trajectories"]
    scenarios = [FleetScenario("resnet50", seed=0),
                 FleetScenario("transformer", seed=1)]
    fronts = {wl: rg._reference_front(space, pool, wl)
              for wl in ("resnet50", "transformer")}
    fr = fleet_service(space, pool, scenarios, q=1, executor="inline",
                       reference_fronts=fronts, metrics=MetricsRegistry(),
                       events=str(tmp_path / "fleet.jsonl"), **rg.RUN_KW)
    for sc, r in zip(fr.scenarios, fr.results):
        assert [int(x) for x in r.evaluated_rows] == \
            pinned[sc.label]["evaluated_rows"], sc.label


# -------------------------------------------------------------- wire layer
def test_wire_metrics_verb_and_status_counters(space, pool96):
    srv = TunerServer(space, pool96, executor="inline",
                      metrics=MetricsRegistry())
    th, port = _serve_in_thread(srv)
    try:
        jid = request(port, {"verb": "submit", "spec": TRANSF})["job"]
        deadline = time.time() + 300
        while time.time() < deadline:
            s = request(port, {"verb": "status"})
            assert s["ok"]
            if s["status"]["jobs"][jid]["status"] == "DONE":
                break
            time.sleep(0.1)
        st = s["status"]
        assert st["jobs"][jid]["status"] == "DONE"
        # satellite: pool fault counters + per-job memo hits on the wire
        assert st["pool"]["retried"] == 0
        assert st["pool"]["abandoned"] == 0
        assert st["jobs"][jid]["memo_hits"] >= 0
        assert st["scheduler"]["cycles"] >= KW["T"]
        assert st["scheduler"]["admissions"] == 1
        m = request(port, {"verb": "metrics"})
        assert m["ok"]
        snap = m["metrics"]
        assert set(snap) == {"counters", "gauges", "histograms"}
        assert snap["counters"]["pool_dispatched_total"]["series"][""] == \
            st["pool"]["dispatched"]
        assert snap["counters"]["scheduler_cycles_total"]["series"][""] == \
            st["cycles"]
        # the snapshot IS the wire payload: client-side --prom rendering
        text = render_prometheus(snap)
        assert "# TYPE pool_dispatched_total counter" in text
        assert "# TYPE scheduler_cycle_seconds histogram" in text
        assert request(port, {"verb": "shutdown"})["ok"]
        th.join(30)
    finally:
        srv.close()


# ------------------------------------------- SIGKILL resume: monotonicity
def test_sigkill_resume_appends_new_generation_with_monotone_counters(
        tmp_path):
    """Satellite 4's crash half: SIGKILL `soc-service serve --events`, then
    --resume into the SAME log. The resume must append a new generation;
    within each generation the scheduler's per-cycle ``counters`` instants
    must never regress; and the combined log must render to a valid
    non-empty Chrome trace through tools/trace_report.py."""
    jobs_file = tmp_path / "jobs.json"
    jobs_file.write_text(json.dumps([
        {"workload": "resnet50", "seed": 0, "q": 2, "min_done": 1, **KW},
        {"workload": "transformer", "seed": 1, "q": 1, **KW}]))
    ev_path = tmp_path / "events.jsonl"
    base = [sys.executable, "-m", "repro.service.cli", "serve",
            "--n-pool", "96", "--pool-seed", "7", "--executor", "thread",
            "--workers", "2", "--jobs-file", str(jobs_file),
            "--checkpoint-dir", str(tmp_path / "ck"),
            "--cache-dir", str(tmp_path / "fc"),
            "--events", str(ev_path), "--drain-exit", "--quiet"]
    env = _cli_env()

    killed = subprocess.run(base + ["--kill-after", "3"], env=env,
                            capture_output=True, text=True, timeout=560)
    assert killed.returncode == -signal.SIGKILL, (killed.returncode,
                                                  killed.stderr)
    resumed = subprocess.run(
        base + ["--resume", "--out", str(tmp_path / "res.json")],
        env=env, capture_output=True, text=True, timeout=560)
    assert resumed.returncode == 0, resumed.stderr
    res = json.loads((tmp_path / "res.json").read_text())["jobs"]
    assert all(j["status"] == "DONE" for j in res.values())

    recs = read_events(str(ev_path))
    by_gen: dict = {}
    for r in recs:
        by_gen.setdefault(r["gen"], []).append(r)
    assert sorted(by_gen) == [0, 1]  # the resume opened generation 1
    assert (tmp_path / "events.jsonl.gen").read_text() == "1"
    for gen, grecs in by_gen.items():
        metas = [r for r in grecs if r["kind"] == "M"]
        assert len(metas) == 1 and metas[0]["run"] == "tuner_server"
        monos = [r["mono"] for r in grecs]
        assert monos == sorted(monos)
        ticks = [r for r in grecs if r["name"] == "counters"]
        assert ticks, f"generation {gen} logged no scheduler counters"
        for fld in ("cycles", "total_done", "dispatched"):
            vals = [t[fld] for t in ticks]
            assert vals == sorted(vals), (
                f"gen {gen}: {fld} regressed within a generation: {vals}")
    # generation 0 died mid-run: its cycle span was torn open by SIGKILL
    trace = build_chrome_trace(recs)
    assert len(trace["traceEvents"]) > 0

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(__file__), "..",
                                     "tools", "trace_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    chrome = tmp_path / "trace.json"
    assert tr.main([str(ev_path), "--quiet",
                    "--chrome", str(chrome)]) == 0
    loaded = json.loads(chrome.read_text())
    assert loaded["traceEvents"]
    assert {e["ph"] for e in loaded["traceEvents"]} <= \
        {"X", "i", "b", "e", "M"}


def test_trace_report_cli_on_empty_log_fails(tmp_path, capsys):
    spec = importlib.util.spec_from_file_location(
        "trace_report_cli", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "trace_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert tr.main([str(empty)]) == 1
    assert "no records" in capsys.readouterr().err
