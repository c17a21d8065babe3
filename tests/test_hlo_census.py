"""HLO kernel census (bcg_tpu/obs/hlo.py + scripts/hlo_census.py) and
its tier-1 drift gate against hlo_baseline.json.

Layers:

1. parser unit tests — kernel-launching-computation selection (entry +
   while body/cond; fusion internals excluded) on handwritten HLO;
2. the hermetic census scenario (module-scoped: three tiny CPU engines,
   one per decode-loop family) matches the checked-in baseline exactly
   — the ROADMAP-item-5 guardrail: a change that adds a kernel to the
   decode step fails HERE, not on hardware months later;
3. the baseline is load-bearing: every entry is exercised, removing an
   entry resurfaces its finding, every entry carries a reason.
"""

import importlib.util
import json
import os

import pytest

from bcg_tpu.obs import counters as obs_counters, hlo as obs_hlo
from bcg_tpu.obs.hlo import COUNT_METRICS, census_from_text

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script():
    path = os.path.join(REPO, "scripts", "hlo_census.py")
    spec = importlib.util.spec_from_file_location("hlo_census", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_HLO = """\
HloModule jit_loop, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

%fused_computation (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8] parameter(0)
  %t = f32[8,8] tanh(f32[8,8] %p0)
  ROOT %g = f32[8,8] gather(f32[8,8] %t, f32[8,8] %t)
}

%region_body (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8,8]) %arg), index=0
  %x = f32[8,8] get-tuple-element((s32[], f32[8,8]) %arg), index=1
  %d = f32[8,8] dot(f32[8,8] %x, f32[8,8] %x)
  %f = f32[8,8] fusion(f32[8,8] %d), kind=kLoop, calls=%fused_computation
  %ar = f32[8,8] all-reduce(f32[8,8] %f), replica_groups={}
  ROOT %tup = (s32[], f32[8,8]) tuple(s32[] %i, f32[8,8] %ar)
}

%region_cond (arg: (s32[], f32[8,8])) -> pred[] {
  %arg = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8,8]) %arg), index=0
  %k = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %k), direction=LT
}

ENTRY %main (p: f32[8,8]) -> f32[8,8] {
  %p = f32[8,8] parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8,8]) tuple(s32[] %z, f32[8,8] %p)
  %w = (s32[], f32[8,8]) while((s32[], f32[8,8]) %init), condition=%region_cond, body=%region_body
  ROOT %out = f32[8,8] get-tuple-element((s32[], f32[8,8]) %w), index=1
}
"""


class TestParser:
    def test_kernel_launching_selection(self):
        c = census_from_text(_HLO)
        # Entry (5 ops incl. the tuple-typed while) + body (7) + cond (4);
        # the fused computation's 3 internal ops are excluded.
        assert c["whiles"] == 1
        assert c["total_ops"] == 16
        assert c["fusions"] == 1          # the body's fusion instruction
        assert c["collectives"] == 1      # all-reduce in the body
        assert c["dots"] == 1
        # gather lives INSIDE the fusion: not a launched kernel.
        assert c["gathers"] == 0

    def test_step_family_is_while_bodies_only(self):
        c = census_from_text(_HLO)
        assert c["step_ops"] == 7
        assert c["step_fusions"] == 1
        assert c["step_dots"] == 1
        assert c["step_collectives"] == 1

    def test_empty_text(self):
        c = census_from_text("")
        assert c["total_ops"] == 0 and c["step_ops"] == 0


@pytest.fixture(scope="module")
def scenario():
    """The real census scenario, once per module (~12 s: three tiny
    engines, one guided call each)."""
    mod = _load_script()
    obs_hlo.reset()
    obs_hlo.enable(True)
    census = mod.run_scenario()
    yield mod, census
    obs_hlo.reset()


class TestCensusScenario:
    def test_all_loop_families_recorded(self, scenario):
        _, census = scenario
        for entry in ("prefill", "prefill_suffix", "decode_loop",
                      "ff_decode_loop", "spec_decode_loop",
                      "prefill_paged", "paged_decode_loop",
                      "paged_pallas_decode_loop",
                      "tpu_paged_decode_loop",
                      "tpu_paged_pallas_decode_loop"):
            assert entry in census, sorted(census)
            assert "error" not in census[entry], census[entry]
            assert census[entry]["total_ops"] > 0
        for entry in ("scan_decode_loop", "scan_prefill_chunk"):
            assert "error" not in census[entry], census[entry]
            assert census[entry]["whiles"] >= 1

    def test_fused_paged_step_kernels_below_gather_baseline(self, scenario):
        """ISSUE-8 acceptance: on the TPU cross-lowering (the kernel's
        real Mosaic lowering — trace+lower needs no hardware), the
        fused paged decode loop's per-step op count is STRICTLY below
        the PR-7 XLA-gather path's, the per-layer attention gather/dot
        chains replaced by exactly one fused kernel custom-call per
        layer.  Both entries are also exact-pinned in hlo_baseline.json,
        so the gap is drift-gated in both directions."""
        _, census = scenario
        gather = census["tpu_paged_decode_loop"]
        fused = census["tpu_paged_pallas_decode_loop"]
        assert fused["step_ops"] < gather["step_ops"], (fused, gather)
        # One fused kernel per layer (tiny-test: 2 layers), none before.
        assert gather["step_custom_calls"] == 0
        assert fused["step_custom_calls"] == 2
        # The attention block gathers and score/value dots folded into
        # the kernel; the remaining gathers (write-path table lookups,
        # embedding, sampler) are common to both arms.
        assert fused["step_gathers"] < gather["step_gathers"]
        assert fused["step_dots"] < gather["step_dots"]

    def test_decode_loops_have_step_kernels(self, scenario):
        _, census = scenario
        for entry in ("decode_loop", "ff_decode_loop", "spec_decode_loop",
                      "paged_decode_loop"):
            assert census[entry]["step_fusions"] > 0
            assert census[entry]["whiles"] >= 1

    def test_cost_analysis_present_on_cpu(self, scenario):
        _, census = scenario
        assert census["prefill"]["flops"] > 0
        assert census["prefill"]["bytes_accessed"] > 0

    def test_gauges_published(self, scenario):
        _, census = scenario
        snap = obs_counters.snapshot()
        assert snap.get("engine.hlo.decode_loop.step_fusions") == \
            census["decode_loop"]["step_fusions"]
        assert snap.get("engine.hlo.prefill.flops") == \
            census["prefill"]["flops"]

    def test_table_renders_per_entry_counts(self, scenario):
        mod, census = scenario
        table = mod.render_table(census)
        assert "fusions" in table and "custom_calls" in table
        assert "decode_loop" in table and "prefill" in table

    def test_scan_step_ops_do_not_grow_with_depth(self, scenario):
        """What ``scan_layers`` is for (36 layers compile as one): the
        scan form's programs hold ONE layer body, so the decode loop's
        and the chunk program's per-step op counts are the same at 2 and
        at 4 layers, while the list form's decode step holds a copy a
        layer.  Last of the class: the two boots leave the recorder
        empty and the ``decode_loop`` gauges theirs (``census`` is the
        fixture's copy)."""
        mod, census = scenario
        shallow, deep = mod.scan_census(2), mod.scan_census(4)
        for entry in ("decode_loop", "prefill_chunk"):
            assert "error" not in deep[entry], deep[entry]
            for metric in ("step_ops", "step_fusions", "step_dots", "whiles"):
                assert shallow[entry][metric] == deep[entry][metric], (
                    entry, metric, shallow[entry], deep[entry]
                )
        assert shallow["decode_loop"]["step_dots"] \
            < census["decode_loop"]["step_dots"]


class TestDriftGate:
    def test_census_matches_checked_in_baseline(self, scenario):
        mod, census = scenario
        findings = mod.check_drift(census, mod.load_baseline())
        assert findings == [], "\n".join(findings)

    def test_added_kernel_in_decode_step_fails(self, scenario):
        """The acceptance-criterion probe: one more kernel in the decode
        step must be a drift finding naming the entry and metric."""
        mod, census = scenario
        mutated = {k: dict(v) for k, v in census.items()}
        mutated["decode_loop"]["step_fusions"] += 1
        mutated["decode_loop"]["step_ops"] += 1
        mutated["decode_loop"]["total_ops"] += 1
        mutated["decode_loop"]["fusions"] += 1
        findings = mod.check_drift(mutated, mod.load_baseline())
        assert any("decode_loop.step_fusions" in f and "added" in f
                   for f in findings), findings

    def test_removing_baseline_entry_resurfaces_finding(self, scenario):
        mod, census = scenario
        baseline = mod.load_baseline()
        assert baseline and baseline["entries"], "baseline missing/empty"
        for entry in list(baseline["entries"]):
            pruned = json.loads(json.dumps(baseline))
            del pruned["entries"][entry]
            findings = mod.check_drift(census, pruned)
            assert any(entry in f and "not pinned" in f for f in findings), (
                entry, findings
            )

    def test_stale_baseline_entry_is_a_finding(self, scenario):
        mod, census = scenario
        baseline = json.loads(json.dumps(mod.load_baseline()))
        baseline["entries"]["no_such_entry"] = {
            "reason": "synthetic", "counts": {"total_ops": 1},
        }
        findings = mod.check_drift(census, baseline)
        assert any("no_such_entry" in f and "stale" in f for f in findings)

    def test_backend_mismatch_refuses_comparison(self, scenario):
        mod, census = scenario
        baseline = json.loads(json.dumps(mod.load_baseline()))
        baseline["backend"] = "tpu"
        findings = mod.check_drift(census, baseline)
        assert len(findings) == 1 and "not comparable" in findings[0]

    def test_every_baseline_entry_has_a_reason(self):
        mod = _load_script()
        baseline = mod.load_baseline()
        for entry, pinned in baseline["entries"].items():
            assert pinned.get("reason", "").strip(), entry

    def test_baseline_pins_every_count_metric(self):
        mod = _load_script()
        baseline = mod.load_baseline()
        for entry, pinned in baseline["entries"].items():
            # An entry pinned by part of the counts says which.
            want = pinned.get("pinned", COUNT_METRICS)
            assert set(pinned["counts"]) == set(want), entry


class TestRecorderHygiene:
    def test_disabled_by_default_records_nothing(self, monkeypatch):
        monkeypatch.delenv("BCG_TPU_HLO_CENSUS", raising=False)
        obs_hlo.reset()
        try:
            sentinel = object()
            assert obs_hlo.wrap("x", sentinel) is sentinel
            obs_hlo.maybe_record("x", None, ())
            assert obs_hlo.snapshot() == {}
        finally:
            obs_hlo.reset()

    def test_recording_failure_is_contained(self):
        obs_hlo.reset()
        obs_hlo.enable(True)
        try:
            class Boom:
                def lower(self, *a, **k):
                    raise RuntimeError("no lowering here")

            obs_hlo.maybe_record("broken_entry", Boom(), (1,))
            snap = obs_hlo.snapshot()
            assert "error" in snap["broken_entry"]
            assert "RuntimeError" in snap["broken_entry"]["error"]
        finally:
            obs_hlo.reset()
