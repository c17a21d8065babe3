"""Fused guided-sampling Pallas kernel (ops/guided_sampler.py).

Four layers of guarantees:

* **Kernel parity** (interpret mode — the same program hardware
  lowers): greedy draws TOKEN-IDENTICAL to the XLA masked-sampler
  reference (engine/speculative.make_masked_sampler) across lane-
  aligned and off-lane vocabs, dead states, exhausted budgets, and the
  speculative loop's ``forbid`` residual; DFA transitions identical.
* **Distribution** (the sampled arm): draws stay inside the reference's
  filtered support and match its renormalized probabilities within 4
  sigma over thousands of seeded draws — the same statistical-contract
  idiom as the speculative loop's residual-distribution checks.
* **Engine integration**: ``fused_sampler="pallas"`` greedy outputs
  identical to the default across the plain, fast-forward, and
  speculative loop families; temp>0 still emits valid guided JSON;
  zero steady-state retraces for the fused loops' (new) jit entry
  keys; the env override and the stats surface agree; the geometry
  guard falls back LOUDLY (naming the knob) only on explicit pallas.
* **The win, gated**: the perf-gate ``sampler`` scenario's parity and
  engagement metrics conform to perf_baseline.json, with the
  load-bearing resurface contract owned here for the sampler.*
  namespace.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bcg_tpu.config import EngineConfig
from bcg_tpu.engine.jax_engine import JaxEngine
from bcg_tpu.engine.speculative import make_masked_logits, make_masked_sampler
from bcg_tpu.obs import counters as obs_counters
from bcg_tpu.ops import guided_sampler as gs

SCHEMA = {
    "type": "object",
    "properties": {
        "decision": {"type": "string", "enum": ["stop", "continue"]},
        "value": {"type": "integer", "minimum": 0, "maximum": 50},
    },
    "required": ["decision", "value"],
    "additionalProperties": False,
}

PROMPTS = [
    ("You are honest agent_1 in a consensus game.",
     "Round 2. agent_2 value: 17. Decide.", SCHEMA),
    ("You are byzantine agent_2 in a consensus game.",
     "Round 2. agent_1 value: 16. Decide.", SCHEMA),
]


def _cfg(**kw):
    return EngineConfig(
        backend="jax", model_name="bcg-tpu/tiny-test", max_model_len=2048,
        **kw,
    )


def _case(rng, B, V, n_dfa, n_states, minb_forbid=0.4):
    """One random sampler-argument set with realistic structure: int16
    tables/min_budget (the GuidedBatch dtypes), dead (-1) states,
    near-exhausted budgets, forbid on a third of the rows."""
    logits = jnp.asarray(rng.randn(B, V).astype(np.float32) * 3)
    tables = jnp.asarray(
        rng.randint(0, n_states, (n_dfa, n_states, V)).astype(np.int16)
    )
    accepting = jnp.asarray(rng.rand(n_dfa, n_states) < 0.5)
    minb = rng.randint(1, 6, (n_dfa, n_states, V)).astype(np.int16)
    minb[rng.rand(n_dfa, n_states, V) < minb_forbid] = np.iinfo(np.int16).max
    args = dict(
        tables=tables, accepting=accepting,
        min_budget=jnp.asarray(minb),
        dfa_ids=jnp.asarray(rng.randint(0, n_dfa, (B,)).astype(np.int32)),
        states=jnp.asarray(rng.randint(-1, n_states, (B,)).astype(np.int32)),
        emitted=jnp.asarray(rng.randint(0, 12, (B,)).astype(np.int32)),
        row_budget=jnp.asarray(rng.randint(2, 16, (B,)).astype(np.int32)),
        forbid=jnp.asarray(np.where(
            rng.rand(B) < 0.33, rng.randint(0, V, B), -1
        ).astype(np.int32)),
    )
    return logits, args


class TestKernelParity:
    """make_fused_sampler (interpret) vs make_masked_sampler, the
    conformance oracle.  Geometries: the tiny-test vocab (512,
    lane-aligned — what every hermetic engine test serves), an off-lane
    vocab (300 — exercises the wrapper's pad path), and a wide-DFA
    shape (the stacked-table form multi-schema batches produce)."""

    GEOMETRIES = [
        pytest.param(512, 2, 8, id="tiny-test-v512"),
        pytest.param(300, 2, 5, id="offlane-v300"),
        pytest.param(256, 4, 40, id="wide-dfa-40-states"),
    ]

    @pytest.mark.parametrize("top_p", [1.0, 0.9])
    @pytest.mark.parametrize("V,n_dfa,n_states", GEOMETRIES)
    def test_greedy_token_identical(self, V, n_dfa, n_states, top_p):
        rng = np.random.RandomState(V + n_states)
        eos = 3
        ref = make_masked_sampler(eos, top_p)
        fused = gs.make_fused_sampler(eos, top_p, interpret=True)
        for trial in range(8):
            logits, a = _case(rng, 8, V, n_dfa, n_states)
            key = jax.random.PRNGKey(trial)
            rt = jnp.zeros(8, jnp.float32)  # all greedy
            t_r, s_r, _ = ref(
                logits, a["states"], key, a["emitted"], a["tables"],
                a["accepting"], a["min_budget"], a["dfa_ids"], rt,
                a["row_budget"], forbid=a["forbid"],
            )
            t_f, s_f, _ = fused(
                logits, a["states"], key, a["emitted"], a["tables"],
                a["accepting"], a["min_budget"], a["dfa_ids"], rt,
                a["row_budget"], forbid=a["forbid"],
            )
            np.testing.assert_array_equal(np.asarray(t_r), np.asarray(t_f))
            np.testing.assert_array_equal(np.asarray(s_r), np.asarray(s_f))

    def test_dead_end_forces_eos(self):
        """A state with no legal token (everything past budget) must
        emit EOS with state -1 — the reference's post-draw override."""
        eos = 3
        fused = gs.make_fused_sampler(eos, 1.0, interpret=True)
        V, B = 256, 4
        logits = jnp.zeros((B, V), jnp.float32)
        minb = jnp.full((1, 2, V), np.iinfo(np.int16).max, jnp.int16)
        tok, states, _ = fused(
            logits, jnp.zeros(B, jnp.int32), jax.random.PRNGKey(0),
            jnp.zeros(B, jnp.int32), jnp.zeros((1, 2, V), jnp.int16),
            jnp.zeros((1, 2), bool), minb, jnp.zeros(B, jnp.int32),
            jnp.zeros(B, jnp.float32), jnp.full((B,), 8, jnp.int32),
        )
        assert (np.asarray(tok) == eos).all()
        assert (np.asarray(states) == -1).all()


class TestTopPDistribution:
    def test_sampled_arm_matches_reference_distribution_4_sigma(self):
        """The fused draw (threshold-scan nucleus + inverse-CDF binary
        search) against the reference's renormalized top-p
        distribution: every kept token's empirical frequency within 4
        sigma over 3000 seeded draws, and NO draw ever lands outside
        the reference's filtered support."""
        eos, top_p, B, V = 3, 0.8, 4, 64
        rng = np.random.RandomState(7)
        fused = gs.make_fused_sampler(eos, top_p, interpret=True)
        ml = make_masked_logits(eos, top_p)
        logits, a = _case(rng, B, V, 1, 4, minb_forbid=0.5)
        states = jnp.maximum(a["states"], 0)
        rt = jnp.full((B,), 0.8, jnp.float32)
        lg, _, _ = ml(
            logits, states, a["emitted"], a["tables"], a["accepting"],
            a["min_budget"], a["dfa_ids"], rt, a["row_budget"],
        )
        lg_np = np.asarray(lg)
        kept = np.isfinite(lg_np)
        probs = np.where(kept, np.exp(lg_np - lg_np.max(-1, keepdims=True)), 0.0)
        probs /= probs.sum(-1, keepdims=True)

        N = 3000
        counts = np.zeros((B, V))
        draw = jax.jit(lambda key: fused(
            logits, states, key, a["emitted"], a["tables"], a["accepting"],
            a["min_budget"], a["dfa_ids"], rt, a["row_budget"],
        )[0])
        for i in range(N):
            t = np.asarray(draw(jax.random.PRNGKey(i)))
            counts[np.arange(B), t] += 1
        # EOS-forced dead rows collapse to a point mass; exclude them
        # from the per-token bands (they trivially pass anyway).
        freq = counts / N
        for b in range(B):
            outside = counts[b][~kept[b]]
            # Dead-end rows force EOS, which may sit outside the mask.
            if probs[b].sum() == 0:
                continue
            assert outside.sum() == 0, f"row {b} drew outside the support"
            for t in range(V):
                p = probs[b, t]
                sd = np.sqrt(max(p * (1 - p), 1e-12) / N)
                assert abs(freq[b, t] - p) <= 4 * sd + 1e-9, (b, t, p, freq[b, t])


class TestEngineIntegration:
    @pytest.mark.parametrize("family_kw", [
        pytest.param({}, id="plain"),
        pytest.param({"decode_fast_forward": True}, id="ff"),
        pytest.param({"spec_decode": True}, id="spec"),
        # The benchmark cell's options: on the chip they run the kernel.
        pytest.param("qwen3-8b-int8", id="cell_options"),
    ])
    def test_greedy_parity_across_loop_families(self, family_kw,
                                                cell_engine_options):
        if isinstance(family_kw, str):
            family_kw = cell_engine_options(family_kw)
            del family_kw["model_name"]     # _cfg's own: the same preset
        ref = JaxEngine(_cfg(**family_kw))
        fused = JaxEngine(_cfg(fused_sampler="pallas", **family_kw))
        try:
            r_ref = ref.batch_generate_json(PROMPTS, temperature=0.0,
                                            max_tokens=48)
            r_fus = fused.batch_generate_json(PROMPTS, temperature=0.0,
                                              max_tokens=48)
        finally:
            ref.shutdown()
            fused.shutdown()
        assert r_ref == r_fus

    def test_sampled_rows_emit_valid_guided_json(self):
        """temp>0 through the fused kernel: the guided mask still
        guarantees parseable schema-conformant output (the seeded e2e
        arm of the distribution contract)."""
        eng = JaxEngine(_cfg(fused_sampler="pallas"))
        try:
            out = eng.batch_generate_json(PROMPTS, temperature=0.9,
                                          max_tokens=48)
        finally:
            eng.shutdown()
        for r in out:
            assert r.get("decision") in ("stop", "continue"), r
            assert 0 <= r.get("value", -1) <= 50, r

    def test_zero_steady_state_retraces_for_fused_entry_keys(self):
        """The fused loops' jit entry keys (loop key + sampler marker)
        pin at zero retraces on an identical-shape warm repeat — the
        fused sampler must not introduce shape-keyed instability."""
        eng = JaxEngine(_cfg(fused_sampler="pallas", spec_decode=True))
        try:
            eng.batch_generate_json(PROMPTS, temperature=0.0, max_tokens=48)
            before = obs_counters.snapshot()
            eng.batch_generate_json(PROMPTS, temperature=0.0, max_tokens=48)
            moved = obs_counters.delta(before)
        finally:
            eng.shutdown()
        jit_movement = {
            k: v for k, v in moved.items()
            if k.startswith(("engine.compile.", "engine.retrace."))
        }
        assert jit_movement == {}, jit_movement

    def test_env_flag_overrides_config_and_stats_reflect(self, monkeypatch):
        monkeypatch.setenv("BCG_TPU_FUSED_SAMPLER", "pallas")
        eng = JaxEngine(_cfg(fused_sampler="xla"))
        try:
            stats = eng.sampler_stats()
            assert stats["impl"] == "pallas"
            assert stats["interpret"] is True  # explicit pallas off-TPU
            assert stats["fused_calls"] == 0  # nothing ran yet
            eng.batch_generate_json(PROMPTS[:1], temperature=0.0,
                                    max_tokens=48)
            assert eng.sampler_stats()["fused_calls"] > 0
            assert eng.sampler_stats()["kv_dtype"] == "bfloat16"
        finally:
            eng.shutdown()

    def test_default_off_tpu_is_xla_and_namespace_clean(self):
        """auto resolves to xla off-TPU: no fused counters, no kernel —
        the configuration every existing baseline was recorded under."""
        eng = JaxEngine(_cfg())
        try:
            assert eng.sampler_stats()["impl"] == "xla"
            eng.batch_generate_json(PROMPTS[:1], temperature=0.0,
                                    max_tokens=48)
            assert eng.sampler_stats()["fused_calls"] == 0
        finally:
            eng.shutdown()


class TestGeometryGuardFallback:
    def test_explicit_pallas_over_guard_warns_naming_the_knob(
        self, monkeypatch
    ):
        """Explicit pallas with a vocab past MAX_VOCAB falls back LOUDLY
        through the shared _kernel_fallback_warn helper — the warning
        must name the causing knob (geometry guard), mirroring the int8
        decode kernel's cause attribution."""
        monkeypatch.setattr(gs, "MAX_VOCAB", 128)  # tiny-test vocab is 512
        with pytest.warns(UserWarning, match="geometry guard"):
            eng = JaxEngine(_cfg(fused_sampler="pallas"))
        try:
            assert eng.sampler_stats()["impl"] == "xla"
        finally:
            eng.shutdown()

    def test_auto_over_guard_is_silent(self, monkeypatch, recwarn):
        monkeypatch.setattr(gs, "MAX_VOCAB", 128)
        eng = JaxEngine(_cfg(fused_sampler="auto"))
        try:
            assert eng.sampler_stats()["impl"] == "xla"
        finally:
            eng.shutdown()
        assert not [
            w for w in recwarn if "fused guided-sampling" in str(w.message)
        ]

    def test_bad_value_raises(self):
        with pytest.raises(ValueError, match="fused_sampler"):
            JaxEngine(_cfg(fused_sampler="vulkan"))


# --------------------------------------------------------- gate-backed
SCRIPT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts", "perf_gate.py")


@pytest.fixture(scope="module")
def sampler_gate_metrics():
    spec = importlib.util.spec_from_file_location("perf_gate", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, mod.run_sampler_scenario()


class TestGateBacked:
    def test_parity_is_exact_and_kernel_engaged(self, sampler_gate_metrics):
        _, m = sampler_gate_metrics
        assert m["sampler.parity_mismatches"] == 0.0
        assert m["sampler.fused_kernel_invocations"] > 0

    def test_metrics_conform_to_perf_baseline(self, sampler_gate_metrics):
        mod, m = sampler_gate_metrics
        findings = mod.check_metrics(m, mod.load_baseline())
        findings += mod.check_stale(m, mod.load_baseline(), ("sampler",))
        assert findings == [], findings

    def test_removing_a_sampler_entry_resurfaces_its_finding(
        self, sampler_gate_metrics
    ):
        mod, m = sampler_gate_metrics
        baseline = mod.load_baseline()
        for removed in m:
            pruned = json.loads(json.dumps(baseline))
            del pruned["metrics"][removed]
            findings = mod.check_metrics(m, pruned)
            assert any(
                removed in f and "no entry" in f for f in findings
            ), (removed, findings)

    def test_injected_parity_regression_is_named(self, sampler_gate_metrics):
        mod, _ = sampler_gate_metrics
        measured = mod.run_sampler_scenario(inject="fail-rows")
        findings = mod.check_metrics(measured, mod.load_baseline())
        assert any("sampler.parity_mismatches" in f for f in findings), findings
