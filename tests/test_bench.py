"""bench.py reports a measurement or fails: no chip, or a phase that
raises, is a non-zero exit with NO metric line on stdout.

``BENCH_r03``–``r05`` entered the driver's record as ``value: 0.0`` with
rc=0 because every failure used to be folded into a JSON line; these
tests pin the opposite contract, and the hermetic fake-backend smoke of
the real attempt path.
"""

import json

import pytest

import bench


@pytest.fixture(autouse=True)
def fake_backend_env(monkeypatch):
    monkeypatch.setenv("BENCH_BACKEND", "fake")
    monkeypatch.delenv("BENCH_MODEL", raising=False)


def _last_json(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_failing_phase_propagates_and_prints_no_metric(monkeypatch, capsys):
    calls = []

    def attempt(*a, **k):
        calls.append(1)
        raise RuntimeError("UNAVAILABLE: connection reset mid-compile")

    monkeypatch.setattr(bench, "_run_attempt", attempt)
    with pytest.raises(RuntimeError, match="UNAVAILABLE"):
        bench.main()
    # No retry-on-"transient" machinery: one attempt, then the error.
    assert len(calls) == 1
    assert capsys.readouterr().out.strip() == ""


def test_no_accelerator_fails_before_boot(monkeypatch, capsys):
    """The real jax backend on a machine whose JAX reports no TPU (this
    one): refused before any engine is built."""
    monkeypatch.setenv("BENCH_BACKEND", "jax")
    monkeypatch.setenv("BENCH_MODEL", "bcg-tpu/tiny-test")
    built = []
    monkeypatch.setattr(
        "bcg_tpu.runtime.orchestrator.BCGSimulation",
        lambda *a, **k: built.append(1),
    )
    with pytest.raises(RuntimeError, match="no accelerator"):
        bench.main()
    assert not built
    assert "metric" not in capsys.readouterr().out


def test_window_without_decode_steps_is_a_failure(monkeypatch, capsys):
    """A real-backend window in which the engine never decoded must not
    become a throughput line (rounds of instant failures read as speed)."""
    monkeypatch.setenv("BENCH_ROUNDS", "1")
    monkeypatch.setenv("BENCH_WARMUP", "1")
    from bcg_tpu.config import BCGConfig
    import dataclasses

    base = BCGConfig()
    cfg = dataclasses.replace(
        base,
        game=dataclasses.replace(base.game, num_honest=3, seed=0),
        engine=dataclasses.replace(base.engine, backend="fake"),
        metrics=dataclasses.replace(
            base.metrics, save_results=False, generate_plots=False),
    )
    # backend label "jax" + force_cpu: the fake engine under it has no
    # total_decode_steps, exactly what an all-failing window looks like.
    with pytest.raises(RuntimeError, match="no decode steps"):
        bench._run_attempt(cfg, "bcg-tpu/tiny-test", "jax", 1, 1, 1,
                           force_cpu=True)
    assert capsys.readouterr().out.strip() == ""


def test_unknown_device_kind_is_an_error():
    """Utilisation is computed only against a sourced peaks row: the
    table holds the v5e, every row names where its numbers are from, and
    a device outside it is refused, not defaulted."""
    assert bench.device_peaks("TPU v5 lite")["hbm_gbps"] == 819.0
    for kind, row in bench.DEVICE_PEAKS.items():
        assert row["source"], kind
    for kind in ("cpu", "TPU v9"):
        with pytest.raises(RuntimeError, match="DEVICE_PEAKS"):
            bench.device_peaks(kind)


def test_fake_backend_end_to_end_smoke(monkeypatch, capsys):
    """The real _run_attempt on the fake backend: one JSON line with the
    contract fields and the knob labels."""
    monkeypatch.setenv("BENCH_ROUNDS", "1")
    monkeypatch.setenv("BENCH_WARMUP", "1")
    bench.main()
    out = _last_json(capsys)
    assert out["metric"] == "agent_decisions_per_sec"
    assert out["value"] > 0
    for key in ("quantization", "kv_cache_dtype", "fast_forward",
                "prefix_caching", "scan_layers", "shared_core_votes",
                "boot_plus_first_round_s", "platform", "device_kind",
                "device_count"):
        assert key in out["extra"]
    # Cold-boot metric is a real measurement, not the None fallback.
    assert out["extra"]["boot_plus_first_round_s"] is not None
