"""Sweep service (bcg_tpu/sweep) — spec expansion, multi-tenant
scheduling, checkpoint/resume, multi-host partitioning, and the
perf_gate 'sweep' scenario's resurface contract (NAMESPACE_OWNERS).

The acceptance criteria asserted here:

* a spec expands to a DETERMINISTIC job list with stable content-hash
  ids (two hosts agree on the partition with no coordination);
* games-as-tenants: per-tenant quotas defer (retry-after) instead of
  rejecting, weighted-fair selection prevents starvation, priority
  classes order strictly;
* one command runs a whole grid to a single aggregated report, and
  re-running the same dir SKIPS completed jobs (resume at job
  granularity) — mid-game rounds resume from the
  BCG_TPU_SERVE_CHECKPOINT_EVERY checkpoints;
* a REAL 2-process CPU cluster partitions the job list, survives a
  SIGKILL mid-sweep, and after resume the merged per-job outcomes
  equal a single-process oracle run of the same spec with ZERO
  duplicate game_end events (consensus_report.duplicate_job_problems).
"""

import glob
import importlib.util
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GATE = os.path.join(REPO, "scripts", "perf_gate.py")
WORKER = os.path.join(REPO, "tests", "_sweep_worker.py")
REPORT = os.path.join(REPO, "scripts", "consensus_report.py")

from bcg_tpu.sweep import (  # noqa: E402
    JOB_DEFAULTS, PRESETS, SweepController, completed_job_ids, expand,
    game_end_jobs, job_id_for, load_spec, render_report, run_sweep,
)

DECISION = {
    "type": "object",
    "properties": {
        "internal_strategy": {"type": "string", "minLength": 1, "maxLength": 25},
        "value": {"type": "integer", "minimum": 0, "maximum": 50},
        "public_reasoning": {"type": "string", "minLength": 1, "maxLength": 25},
    },
    "required": ["internal_strategy", "value", "public_reasoning"],
    "additionalProperties": False,
}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ spec layer


class TestSpecExpansion:
    def test_expansion_is_deterministic(self):
        spec = {
            "axes": {
                "seed": [0, 1], "agents": [4, 6],
                "topology": ["ring", "fully_connected"],
            }
        }
        a = expand(spec)
        b = expand(spec)
        assert [j.job_id for j in a] == [j.job_id for j in b]
        assert len(a) == 8
        # Sorted-axis-name expansion order: agents varies slowest
        # (a < s < t alphabetically: agents, seed, topology).
        assert [j.params["agents"] for j in a] == [4] * 4 + [6] * 4

    def test_job_ids_are_content_hashes(self):
        # Same resolved params -> same id regardless of spec shape.
        via_axes = expand({"axes": {"seed": [3]}, "base": {"agents": 6}})[0]
        via_base = expand({"base": {"seed": 3, "agents": 6}, "axes": {}})[0]
        assert via_axes.job_id == via_base.job_id
        params = dict(JOB_DEFAULTS, seed=3, agents=6)
        assert via_axes.job_id == job_id_for(params)

    def test_unknown_axis_is_an_error(self):
        with pytest.raises(ValueError, match="unknown axis"):
            expand({"axes": {"agnets": [4]}})
        with pytest.raises(ValueError, match="unknown base"):
            expand({"base": {"topologyy": "ring"}, "axes": {}})

    def test_duplicate_job_is_an_error(self):
        with pytest.raises(ValueError, match="duplicate job"):
            expand({"axes": {"seed": [1, 1]}})

    def test_paper_grid_preset_is_acceptance_scale(self):
        jobs = expand(PRESETS["paper-grid"])
        assert len(jobs) >= 100
        assert len({j.job_id for j in jobs}) == len(jobs)
        agents = {j.params["agents"] for j in jobs}
        topos = {j.params["topology"] for j in jobs}
        assert len(agents) >= 2 and len(topos) >= 2  # mixed, per ROADMAP

    def test_to_config_maps_every_knob(self):
        job = expand({
            "base": {
                "agents": 6, "byzantine": 2, "topology": "ring",
                "seed": 9, "max_rounds": 3, "backend": "fake",
                "decide_tokens": 40, "vote_tokens": 20,
            },
            "axes": {},
        })[0]
        cfg = job.to_config()
        assert cfg.game.num_honest == 4 and cfg.game.num_byzantine == 2
        assert cfg.network.topology_type == "ring"
        assert cfg.game.seed == 9 and cfg.game.max_rounds == 3
        assert cfg.llm.max_tokens_decide == 40
        assert cfg.metrics.save_results is False

    def test_load_spec_preset_and_file(self, tmp_path):
        assert load_spec("smoke")["name"] == "smoke"
        p = tmp_path / "s.json"
        p.write_text(json.dumps({"axes": {"seed": [0]}}))
        assert load_spec(str(p))["axes"] == {"seed": [0]}
        with pytest.raises(ValueError, match="axes"):
            bad = tmp_path / "bad.json"
            bad.write_text("[]")
            load_spec(str(bad))


# ------------------------------------------------- tenant scheduling unit


class TestTenantScheduling:
    def _scheduler(self, **kw):
        from bcg_tpu.engine.fake import FakeEngine
        from bcg_tpu.serve.scheduler import Scheduler

        kw.setdefault("linger_ms", 0)
        kw.setdefault("max_queue_rows", 4096)
        kw.setdefault("deadline_ms", 0)
        return Scheduler(FakeEngine(seed=0, policy="consensus"), **kw)

    def _plug(self, sched):
        release = threading.Event()
        plugged = threading.Event()

        def hold():
            plugged.set()
            release.wait()

        t = threading.Thread(target=lambda: sched.run_exclusive(hold))
        t.start()
        assert plugged.wait(10)
        return release, t

    def _row(self, tag="x"):
        return ("sys", f"{tag} Your current value: 17. Decide.", DECISION)

    def _drain(self, sched):
        deadline = time.monotonic() + 10
        while sched.queue_depth_rows() > 0:
            assert time.monotonic() < deadline
            time.sleep(0.001)

    def test_quota_defers_with_retry_after(self):
        from bcg_tpu.serve.scheduler import AdmissionDeferred

        sched = self._scheduler()
        t = sched.register_tenant("job-a", quota_rows=4)
        release, plug = self._plug(sched)
        try:
            first = sched.submit(("json",), [self._row()] * 2, [0.0] * 2,
                                 [64] * 2, tenant="job-a")
            self._drain(sched)
            second = sched.submit(("json",), [self._row()] * 4, [0.0] * 4,
                                  [64] * 4, tenant="job-a")
            assert second.error is None  # exactly at quota: admitted
            over = sched.submit(("json",), [self._row()], [0.0], [64],
                                tenant="job-a")
            assert isinstance(over.error, AdmissionDeferred)
            assert over.error.retry_after_s > 0
        finally:
            release.set()
            plug.join(10)
        assert first.done.wait(30) and second.done.wait(30)
        sched.close()
        assert t.max_queued_rows <= 4  # quota exactness
        assert t.deferrals == 1
        snap = sched.snapshot()
        assert snap["deferred"] == 1
        assert snap["tenants"]["job-a"]["quota_rows"] == 4

    def test_weighted_fairness_orders_batch_selection(self):
        sched = self._scheduler(bucket_rows=4, strict_admission=False)
        sched.register_tenant("big", weight=1.0)
        sched.register_tenant("small", weight=1.0)
        release, plug = self._plug(sched)
        try:
            seed = sched.submit(("json",), [self._row("b")] * 4, [0.0] * 4,
                                [64] * 4, tenant="big")
            self._drain(sched)
            reqs = [sched.submit(("json",), [self._row("b")] * 4,
                                 [0.0] * 4, [64] * 4, tenant="big")
                    for _ in range(3)]
            small = sched.submit(("json",), [self._row("s")] * 4, [0.0] * 4,
                                 [64] * 4, tenant="small")
        finally:
            release.set()
            plug.join(10)
        for r in [seed, small] + reqs:
            assert r.done.wait(30)
        sched.close()
        # small's vtime (0) beat big's (4 after the seed batch): it
        # dispatched before at least two queued big requests.
        snap = sched.snapshot()
        assert snap["tenants"]["small"]["served_rows"] == 4
        assert snap["completed"] == 5

    def test_priority_class_beats_fairness(self):
        sched = self._scheduler(bucket_rows=4, strict_admission=False)
        sched.register_tenant("lowprio", priority=0)
        sched.register_tenant("highprio", priority=5)
        # The order of dispatch, read where it happens: the tag of each
        # batch the engine is handed (two threads woken by two `done`
        # events race under load).
        order = []
        serve = sched._engine.batch_generate_json

        def recording(prompts, *args, **kw):
            order.append(prompts[0][1][0])
            return serve(prompts, *args, **kw)

        sched._engine.batch_generate_json = recording
        release, plug = self._plug(sched)
        try:
            seed = sched.submit(("json",), [self._row("l")] * 4, [0.0] * 4,
                                [64] * 4, tenant="lowprio")
            self._drain(sched)
            lo = sched.submit(("json",), [self._row("l")] * 4, [0.0] * 4,
                              [64] * 4, tenant="lowprio")
            hi = sched.submit(("json",), [self._row("h")] * 4, [0.0] * 4,
                              [64] * 4, tenant="highprio")
        finally:
            release.set()
            plug.join(10)
        for r in (seed, lo, hi):
            assert r.done.wait(30)
        sched.close()
        # highprio submitted AFTER lowprio but dispatched first.
        assert order == ["l", "h", "l"], order

    def test_untenanted_requests_share_one_fair_account(self):
        """On a tenanted scheduler, untenanted (and unregistered-name)
        requests charge ONE shared anonymous account — they accrue
        virtual time like everyone else instead of keeping a permanent
        vtime of 0 that would outrank every tenant with history."""
        sched = self._scheduler()
        sched.register_tenant("job-x")
        out = sched.submit_and_wait(("json",), [self._row()] * 3,
                                    [0.0] * 3, [64] * 3)
        assert len(out) == 3
        assert sched._anon_tenant.served_rows == 3
        # Unregistered tenant names ride the same shared account.
        sched.submit_and_wait(("json",), [self._row()], [0.0], [64],
                              tenant="never-registered")
        assert sched._anon_tenant.served_rows == 4
        snap = sched.snapshot()
        assert "(untenanted)" not in snap["tenants"]
        sched.close()

    def test_default_tenant_behavior_unchanged(self):
        """No registered tenants: snapshot carries tenants=None and
        dispatch is the pre-tenancy FIFO (submit order preserved)."""
        sched = self._scheduler()
        out = sched.submit_and_wait(("json",), [self._row()], [0.0], [64])
        assert isinstance(out[0], dict) and "error" not in out[0]
        snap = sched.snapshot()
        assert snap["tenants"] is None
        assert snap["deferred"] == 0
        sched.close()

    def test_serving_engine_retries_deferrals_transparently(self):
        """A ServingEngine tenant over quota backs off and completes —
        the game thread sees latency, never AdmissionDeferred."""
        from bcg_tpu.serve.engine import ServingEngine

        sched = self._scheduler()
        sched.register_tenant("jobq", quota_rows=2)
        proxy = ServingEngine(sched._engine, scheduler=sched, tenant="jobq")
        outs = []

        def call():
            outs.append(proxy.batch_generate_json(
                [self._row()] * 2, temperature=0.0, max_tokens=64
            ))

        threads = [threading.Thread(target=call) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        sched.close()
        assert len(outs) == 4
        assert all("error" not in row for out in outs for row in out)

    def test_retry_after_derivation_monotone(self):
        from bcg_tpu.serve.scheduler import derive_retry_after_ms

        grid = [derive_retry_after_ms(20.0, 10.0, slo_ms=50,
                                      headroom_p50_ms=float(h))
                for h in range(0, 51, 5)]
        assert all(a >= b for a, b in zip(grid, grid[1:]))
        assert grid[0] == pytest.approx(4.0 * grid[-1])
        # No SLO: plain base, floored at 1 ms.
        assert derive_retry_after_ms(0.0, 0.0) == 1.0
        assert derive_retry_after_ms(25.0, 10.0) == 25.0


# --------------------------------------------------- single-process sweep


class TestSingleProcessSweep:
    def test_smoke_sweep_runs_and_resumes(self, tmp_path):
        out = str(tmp_path / "sweep")
        s = run_sweep("smoke", out, linger_ms=0)
        assert s["jobs"] == 4 and s["completed"] == 4 and s["failed"] == 0
        # Manifest: fleet-identity-stamped header + job lifecycle.
        man = [json.loads(l) for l in
               open(os.path.join(out, "sweep-manifest-r0.jsonl"))]
        header = next(r for r in man if r["event"] == "manifest")
        for key in ("run_id", "host", "process_index", "flags", "sweep"):
            assert key in header, sorted(header)
        ends = [r for r in man if r["event"] == "job_end"]
        assert len(ends) == 4
        assert all(r["status"] == "completed" for r in ends)
        # Event stream: every game carries its job id on start/end.
        events = [json.loads(l) for p in
                  glob.glob(os.path.join(out, "events-*.jsonl"))
                  for l in open(p)]
        game_ends = [r for r in events if r.get("event") == "game_end"]
        assert len(game_ends) == 4
        assert {r["job"] for r in game_ends} == set(completed_job_ids(out))
        # Resume: a second run of the same spec skips everything.
        s2 = run_sweep("smoke", out, linger_ms=0)
        assert s2["skipped"] == 4 and s2["completed"] == 0
        game_ends2 = [
            r for p in glob.glob(os.path.join(out, "events-*.jsonl"))
            for l in open(p)
            for r in [json.loads(l)] if r.get("event") == "game_end"
        ]
        assert len(game_ends2) == 4  # zero duplicate game_end
        report = render_report(out)
        assert "4 jobs ended" in report
        assert "100.0%" in report

    def test_game_end_recovery_closes_the_manifest_gap(self, tmp_path):
        """A game_end on disk without its manifest job_end (the kill
        window) must mark the job completed on resume, not rerun it."""
        out = str(tmp_path / "sweep")
        run_sweep("smoke", out, linger_ms=0)
        man_path = os.path.join(out, "sweep-manifest-r0.jsonl")
        records = [json.loads(l) for l in open(man_path)]
        dropped = next(r for r in records if r["event"] == "job_end")
        with open(man_path, "w") as f:
            for r in records:
                if not (r["event"] == "job_end"
                        and r["job"] == dropped["job"]):
                    f.write(json.dumps(r) + "\n")
        assert dropped["job"] not in completed_job_ids(out)
        assert dropped["job"] in game_end_jobs(out)
        s2 = run_sweep("smoke", out, linger_ms=0)
        assert s2["skipped"] == 4 and s2["completed"] == 0
        recovered = completed_job_ids(out)[dropped["job"]]
        assert recovered.get("recovered") is True

    def test_mid_game_round_checkpoint_resume(self, tmp_path, monkeypatch):
        """A job interrupted mid-game resumes from its newest round
        checkpoint: the resumed game continues (not restarts) and the
        outcome matches an uninterrupted oracle run."""
        monkeypatch.setenv("BCG_TPU_SERVE_CHECKPOINT_EVERY", "1")
        # The stubborn policy never converges, so the game reliably
        # outlives the 2-round interruption point (max_rounds 6).
        spec = {"name": "ckpt", "base": {"agents": 4, "byzantine": 1,
                                         "max_rounds": 6, "seed": 0,
                                         "fake_policy": "stubborn"},
                "axes": {}}
        oracle_dir = str(tmp_path / "oracle")
        o = run_sweep(spec, oracle_dir, linger_ms=0)
        oracle = o["results"][0]

        out = str(tmp_path / "interrupted")
        ctl = SweepController(spec, out, linger_ms=0)
        job = ctl.jobs[0]
        # Simulate the kill: run the game 2 rounds, checkpoint, abandon.
        os.makedirs(out, exist_ok=True)
        cfg = job.to_config()
        import dataclasses

        from bcg_tpu.runtime.orchestrator import BCGSimulation

        job_dir = os.path.join(out, "jobs", job.job_id)
        cfg = dataclasses.replace(cfg, metrics=dataclasses.replace(
            cfg.metrics, results_dir=job_dir))
        sim = BCGSimulation(config=cfg, sweep_job_id=job.job_id)
        sim.run_round()
        sim.run_round()
        assert not sim.game.game_over
        sim.close()
        assert glob.glob(os.path.join(job_dir, "checkpoints", "*.json"))
        # Resume through the controller: must pick the checkpoint up.
        s = run_sweep(spec, out, linger_ms=0)
        assert s["completed"] == 1
        result = s["results"][0]
        assert result.get("resumed_from_round", 0) >= 3
        assert result["converged"] == oracle["converged"]
        assert result["rounds"] == oracle["rounds"]

    def test_cli_run_expand_report(self, tmp_path, capsys):
        from bcg_tpu.sweep.__main__ import main

        assert main(["list"]) == 0
        assert "paper-grid" in capsys.readouterr().out
        assert main(["expand", "smoke"]) == 0
        lines = capsys.readouterr().out.strip().splitlines()
        assert len(lines) == 4 and all(
            json.loads(l)["job"].startswith("j") for l in lines
        )
        out = str(tmp_path / "cli")
        assert main(["run", "smoke", "--out", out]) == 0
        text = capsys.readouterr().out
        assert "sweep smoke" in text and "sweep report" in text
        assert main(["report", out]) == 0
        assert "jobs ended" in capsys.readouterr().out

    def test_consensus_report_merges_sweep_events(self, tmp_path, capsys):
        """The sweep dir's event files flow through the existing
        manifest-grouped merge; duplicate-job detection stays silent on
        a clean sweep and fires on a doctored duplicate."""
        out = str(tmp_path / "sweep")
        run_sweep("smoke", out, linger_ms=0)
        cr = _load(REPORT, "consensus_report_sweep")
        paths = sorted(glob.glob(os.path.join(out, "events-*.jsonl")))
        problems = []
        games = []
        for p in paths:
            games.extend(cr.parse_file(p, problems))
        assert sum(1 for g in games if g.ended) == 4
        assert cr.duplicate_job_problems(games) == []
        # Doctor a duplicate: the same file parsed twice = every job
        # ended twice.
        twice = []
        for p in paths + paths:
            twice.extend(cr.parse_file(p, []))
        dups = cr.duplicate_job_problems(twice)
        assert len(dups) == 4 and "ran to completion twice" in dups[0]


# ------------------------------------------------------- perf_gate sweep


@pytest.fixture(scope="module")
def sweep_gate():
    mod = _load(GATE, "perf_gate_sweep")
    return mod, mod.run_sweep_scenario()


class TestSweepGate:
    def test_gate_green_at_head(self, sweep_gate):
        mod, measured = sweep_gate
        findings = mod.check_metrics(measured, mod.load_baseline())
        findings += mod.check_stale(measured, mod.load_baseline(), ("sweep",))
        assert findings == [], "\n".join(findings)

    def test_scenario_measures_the_advertised_metrics(self, sweep_gate):
        _, measured = sweep_gate
        for name in (
            "sweep.starvation_ratio", "sweep.fairness_batches",
            "sweep.quota_overrun_rows", "sweep.quota_deferrals",
            "sweep.retry_after_live_ms", "sweep.retry_after_monotonicity",
            "sweep.error_rows",
        ):
            assert name in measured, sorted(measured)
        assert measured["sweep.quota_overrun_rows"] == 0.0
        assert measured["sweep.retry_after_monotonicity"] == 1.0

    def test_removing_entry_resurfaces_unbaselined_failure(self, sweep_gate):
        mod, measured = sweep_gate
        baseline = mod.load_baseline()
        del baseline["metrics"]["sweep.starvation_ratio"]
        findings = mod.check_metrics(measured, baseline)
        assert any("sweep.starvation_ratio" in f and "no entry" in f
                   for f in findings), findings

    def test_fairness_off_injection_names_the_metric(self, sweep_gate):
        mod, _ = sweep_gate
        measured = mod.run_sweep_scenario("fairness-off")
        findings = mod.check_metrics(measured, mod.load_baseline())
        assert any("sweep.starvation_ratio" in f for f in findings), findings


# ----------------------------------------------- 2-process cluster sweep


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _cluster_env(out_dir, run_id, linger_ms):
    return dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        PYTHONPATH=REPO,
        BCG_TPU_RUN_ID=run_id,
        BCG_TPU_SERVE_CHECKPOINT_EVERY="1",
        BCG_TPU_SERVE_LINGER_MS=str(linger_ms),
    )


def _launch_cluster(out_dir, spec_path, run_id, linger_ms):
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, coord, "2", str(pid), out_dir,
             spec_path],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=_cluster_env(out_dir, run_id, linger_ms), cwd=REPO,
        ))
    return procs


def _outcomes_by_job(event_paths):
    """job -> (converged, rounds_to_consensus) over ENDED games, via
    the real consensus_report parser (the merge consumers use)."""
    cr = _load(REPORT, "consensus_report_cluster")
    games = []
    problems = []
    for p in event_paths:
        games.extend(cr.parse_file(p, problems))
    dups = cr.duplicate_job_problems(games)
    assert dups == [], dups
    return {
        g.job: (g.converged, g.rounds_to_consensus)
        for g in games if g.ended and g.job
    }, games


CLUSTER_SPEC = {
    "name": "cluster-grid",
    "base": {"max_rounds": 6, "byzantine": 0},
    "axes": {
        "agents": [4, 5],
        "fake_policy": ["consensus", "stubborn"],
        "seed": [0, 1, 2],
    },
}


class TestTwoProcessSweep:
    def test_kill_resume_matches_single_process_oracle(self, tmp_path):
        """The acceptance run: 12 jobs partitioned over a REAL
        2-process JAX CPU cluster, SIGKILLed mid-sweep, resumed with a
        second launch into the same dir — the completed job set is
        identical to the spec, no job ran twice (zero duplicate
        game_end), and per-job outcomes equal a single-process oracle
        run."""
        out = str(tmp_path / "cluster")
        os.makedirs(out)
        spec_path = str(tmp_path / "spec.json")
        with open(spec_path, "w") as f:
            json.dump(CLUSTER_SPEC, f)

        # Phase 1: launch with a slowed scheduler (40 ms linger per
        # dispatch) and SIGKILL both ranks once >= 2 jobs completed.
        procs = _launch_cluster(out, spec_path, "sweeptestrun1", 40)
        deadline = time.monotonic() + 120
        try:
            while time.monotonic() < deadline:
                if len(completed_job_ids(out)) >= 2:
                    break
                if all(p.poll() is not None for p in procs):
                    break  # sweep finished before the kill landed
                time.sleep(0.002)
            for p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGKILL)
        finally:
            for p in procs:
                try:
                    p.communicate(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
        after_kill = set(completed_job_ids(out))

        # Phase 2: resume into the same dir (full speed).
        procs = _launch_cluster(out, spec_path, "sweeptestrun2", 0)
        outs = [p.communicate(timeout=240)[0] for p in procs]
        for pid, (p, text) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {pid}:\n{text[-3000:]}"
        summaries = [
            json.loads(line.split("SWEEP-OK ", 1)[1])
            for text in outs
            for line in text.splitlines() if line.startswith("SWEEP-OK")
        ]
        assert len(summaries) == 2
        assert all(s["failed"] == 0 for s in summaries)
        assert {s["rank"] for s in summaries} == {0, 1}
        # Strided partition: 6 jobs per rank, every job accounted.
        assert all(s["partition"] == 6 for s in summaries)
        assert all(
            s["completed"] + s["skipped"] == s["partition"]
            for s in summaries
        )

        jobs = {j.job_id for j in expand(CLUSTER_SPEC)}
        done = completed_job_ids(out)
        assert set(done) == jobs  # identical job set, nothing missing
        assert after_kill <= set(done)

        # Oracle: the same spec, one process, fresh dir.
        oracle_dir = str(tmp_path / "oracle")
        o = run_sweep(CLUSTER_SPEC, oracle_dir, linger_ms=0)
        assert o["completed"] == 12 and o["failed"] == 0
        oracle_map, _ = _outcomes_by_job(
            sorted(glob.glob(os.path.join(oracle_dir, "events-*.jsonl")))
        )
        merged_map, games = _outcomes_by_job(
            sorted(glob.glob(os.path.join(out, "events-*.jsonl")))
        )
        assert merged_map == oracle_map  # merged report == oracle
        assert set(merged_map) == jobs
        # The deterministic policies split exactly: consensus games
        # converge, stubborn games never do.
        assert sum(1 for c, _ in merged_map.values() if c) == 6

    def test_cooperative_single_job_records_once(self, tmp_path):
        """A single-job sweep on the 2-process group runs
        cooperatively: both ranks play the SAME game and only rank 0
        records it — the merged report counts ONE game.  (The
        spmd_exchange arm of cooperative mode — exchange_values_global
        over the dp-across-hosts mesh — needs a backend with
        cross-process collectives; this CPU backend refuses
        multiprocess computations, same reason test_multihost.py is
        hardware-gated.  Its semantics are pinned single-process in
        test_parallel.py.)"""
        out = str(tmp_path / "coop")
        os.makedirs(out)
        spec = {
            "name": "coop",
            "base": {"agents": 4, "byzantine": 0, "max_rounds": 3,
                     "seed": 1},
            "axes": {},
        }
        spec_path = str(tmp_path / "coop.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        procs = _launch_cluster(out, spec_path, "sweepcooprun", 0)
        outs = [p.communicate(timeout=240)[0] for p in procs]
        for pid, (p, text) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {pid}:\n{text[-3000:]}"
        summaries = [
            json.loads(line.split("SWEEP-OK ", 1)[1])
            for text in outs
            for line in text.splitlines() if line.startswith("SWEEP-OK")
        ]
        assert all(s["cooperative"] for s in summaries)
        assert all(s["completed"] == 1 for s in summaries)
        # One manifest (rank 0's), one game in the merged events.
        assert glob.glob(os.path.join(out, "sweep-manifest-r*.jsonl")) == [
            os.path.join(out, "sweep-manifest-r0.jsonl")
        ]
        merged_map, games = _outcomes_by_job(
            sorted(glob.glob(os.path.join(out, "events-*.jsonl")))
        )
        assert len(merged_map) == 1
        # Both ranks computed the identical deterministic outcome.
        (outcome,) = merged_map.values()
        assert outcome[0] is True  # 4 honest consensus-policy agents


# ----------------------------------------------------- acceptance (slow)


@pytest.mark.slow
def test_hundred_game_sweep_single_command(tmp_path):
    """ISSUE acceptance: one command runs the >= 100-job paper-grid
    (mixed agent counts / topologies / seeds) on the virtual-device CPU
    mesh to a single aggregated report."""
    from bcg_tpu.sweep.__main__ import main

    out = str(tmp_path / "grid")
    assert main(["run", "paper-grid", "--out", out, "--json"]) == 0
    done = completed_job_ids(out)
    assert len(done) == len(expand(PRESETS["paper-grid"])) >= 100
    report = render_report(out)
    assert "jobs ended" in report
    events = sorted(glob.glob(os.path.join(out, "events-*.jsonl")))
    game_ends = [
        r for p in events for l in open(p)
        for r in [json.loads(l)] if r.get("event") == "game_end"
    ]
    assert len(game_ends) == len(done)  # zero duplicates at scale
