#!/usr/bin/env python
"""HLO kernel census over the engine's jit entries, hermetically on CPU.

Builds tiny hermetic engines (``bcg-tpu/tiny-test``), enables the
census recorder (``bcg_tpu/obs/hlo.py``), drives one deterministic
guided generation per decode-loop family (plain / fast-forward /
speculative), and prints the per-entry op census — fusions,
custom-calls, collectives, scatter/gather, per-decode-step kernel
counts — plus XLA cost-analysis FLOPs and bytes-accessed.  This is
ROADMAP item 5's acceptance instrument: any Pallas fusion work must
move ``decode_loop.step_fusions``/``step_ops`` DOWN, and nothing may
move them up unnoticed.

Drift gate: ``--check`` compares the census against the checked-in
``hlo_baseline.json`` (same justified-entry idiom as
``lint_baseline.json`` — every entry carries a reason, a censused entry
missing from the baseline is a finding, a baseline entry the scenario
no longer exercises is a stale-entry finding) and exits non-zero on any
drift, so it composes with ``set -o pipefail`` harnesses and tier-1
(``tests/test_hlo_census.py`` runs the same comparison in-process).
``--update-baseline`` regenerates the file, PRESERVING existing
reasons.

Usage:
    python scripts/hlo_census.py                 # print the table
    python scripts/hlo_census.py --check         # drift-gate (rc 2 on drift)
    python scripts/hlo_census.py --update-baseline
    python scripts/hlo_census.py --json          # machine-readable census
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ARMS = ("scan", "plain", "ff", "spec", "paged", "paged_pallas", "fused")
# What the scan arm's entries pin: the structure a layer scan exists
# for, not the CPU compiler's fusion choices inside it.
SCAN_PINNED = ("whiles", "step_collectives", "step_custom_calls")
_MODEL = "bcg-tpu/tiny-test"
_SCHEMA = {
    "type": "object",
    "properties": {"value": {"type": "integer", "minimum": 0, "maximum": 100}},
    "required": ["value"],
}
# Deterministic two-row scenario: one system prefix (prefix-cache path
# compiles prefill_suffix too) + a short round prompt; temperature 0.
_PROMPTS = [
    ("You are agent_1 in a consensus game.",
     "Round 1. agent_2 value: 41. Your current value: 42. Decide."),
    ("You are agent_2 in a consensus game.",
     "Round 1. agent_1 value: 42. Your current value: 41. Decide."),
]


def baseline_path() -> str:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(root, "hlo_baseline.json")


def _force_cpu() -> None:
    # Hermetic: the census pins CPU-lowered programs.  Pin the same
    # 8-device virtual CPU mesh tests/conftest.py asks for: XLA's fusion
    # decisions depend on the host-platform device count, so the
    # baseline is only comparable to tier-1's in-process census if both
    # lower under identical geometry.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()


def run_scenario(arms=ARMS) -> Dict[str, Dict]:
    """Drive the census scenario and return ``hlo.snapshot()``.

    One tiny engine per decode-loop family; entries shared between arms
    (the prefill family) record from whichever arm runs first — arm
    order is fixed, so the census is deterministic.
    """
    _force_cpu()
    from bcg_tpu.config import BCGConfig
    from bcg_tpu.engine.jax_engine import JaxEngine
    from bcg_tpu.obs import hlo as obs_hlo

    # The scan arm records under the engine's own entry names
    # (decode_loop, prefill_chunk), which the list-form arms use too: it
    # runs first, alone in the recorder, and its records are merged
    # below under names of their own.
    scan = scan_census() if "scan" in arms else {}
    obs_hlo.enable(True)
    base = BCGConfig().engine
    for arm in arms:
        if arm == "scan":
            continue
        cfg = dataclasses.replace(
            base,
            model_name=_MODEL,
            backend="jax",
            max_model_len=512,
            decode_fast_forward=(arm == "ff"),
            spec_decode=(arm == "spec"),
            # The paged arms lower the block-gather/scatter programs
            # under their own entry names (prefill_paged /
            # paged_decode_loop / paged_pallas_decode_loop) so the
            # dense entries never drift.  The paged_pallas arm runs the
            # fused kernel in interpret mode (this census is CPU) — its
            # step counts are gated strictly BELOW the gather arm's
            # (tests/test_hlo_census.py), the ISSUE-8 acceptance hook.
            paged_kv=arm.startswith("paged"),
            paged_kv_impl=("pallas" if arm == "paged_pallas" else "auto"),
            # The fused arm EXECUTES the fused-sampler plain loop (the
            # kernel's interpret-mode emulation on this CPU census —
            # its entry pins under fused_decode_loop with a NOT-kernels
            # reason, like paged_pallas).  The hardware inequality —
            # step ops strictly DOWN under the fused sampler for all
            # three loop families — is carried by the tpu_*/tpu_fused_*
            # cross-lowering twin entries the dense arms record
            # (engine._maybe_record_sampler_tpu_lowering).
            fused_sampler=("pallas" if arm == "fused" else "auto"),
        )
        engine = JaxEngine(cfg)
        try:
            engine.batch_generate_json(
                [(sysp, user, _SCHEMA) for sysp, user in _PROMPTS],
                temperature=0.0, max_tokens=24,
            )
        finally:
            engine.shutdown()
    census = obs_hlo.snapshot()
    for entry in ("decode_loop", "prefill_chunk"):
        if entry in scan:
            rec = scan[entry]
            census[f"scan_{entry}"] = (
                rec if "error" in rec
                else {**{m: rec[m] for m in SCAN_PINNED + ("backend",)},
                      "pinned": list(SCAN_PINNED)}
            )
    return census


def scan_census(num_layers: Optional[int] = None) -> Dict[str, Dict]:
    """The census of one guided call in the form the benchmark's cells
    compile: layers under ``lax.scan`` over a stacked int8 cache, the
    prompt through the chunk program.  Entry names are the engine's own
    (``decode_loop``, ``prefill_chunk``), so this starts from an empty
    recorder and leaves one behind: whatever the recorder held is
    dropped.  ``num_layers`` deepens the tiny spec: a scan's programs
    must not grow with it."""
    _force_cpu()
    from bcg_tpu.config import BCGConfig
    from bcg_tpu.engine.jax_engine import JaxEngine
    from bcg_tpu.models.configs import spec_for_model
    from bcg_tpu.obs import hlo as obs_hlo

    spec = spec_for_model(_MODEL)
    if num_layers is not None:
        spec = dataclasses.replace(spec, num_layers=num_layers)
    cfg = dataclasses.replace(
        BCGConfig().engine, model_name=_MODEL, backend="jax",
        max_model_len=512, scan_layers=True, kv_cache_dtype="int8",
        prefill_chunk=64, prefix_caching=False,
    )
    obs_hlo.reset()
    obs_hlo.enable(True)
    engine = JaxEngine(cfg, spec=spec)
    try:
        engine.batch_generate_json(
            [(sysp, user, _SCHEMA) for sysp, user in _PROMPTS],
            temperature=0.0, max_tokens=24,
        )
        return obs_hlo.snapshot()
    finally:
        engine.shutdown()
        obs_hlo.reset()


# ---------------------------------------------------------------- baseline
def load_baseline(path: Optional[str] = None) -> Optional[Dict]:
    path = path or baseline_path()
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def check_drift(census: Dict[str, Dict], baseline: Optional[Dict]) -> List[str]:
    """Findings (empty = green) comparing a census against the baseline.

    Count metrics compare EXACTLY (op counts of a fixed program on a
    fixed backend are deterministic; one added kernel in the decode step
    must fail).  flops / bytes_accessed compare within the baseline's
    relative tolerance (default 10%) — cost-model outputs, pinned
    loosely on purpose.
    """
    from bcg_tpu.obs.hlo import COUNT_METRICS

    if baseline is None:
        return [f"no baseline file at {baseline_path()} — run "
                "scripts/hlo_census.py --update-baseline"]
    findings: List[str] = []
    import jax

    backend = jax.default_backend()
    if baseline.get("backend") != backend:
        return [
            f"baseline was recorded on backend {baseline.get('backend')!r} "
            f"but this census ran on {backend!r} — not comparable; "
            "regenerate with --update-baseline on the target backend"
        ]
    version_note = ""
    if baseline.get("jax_version") != jax.__version__:
        version_note = (
            f" [note: baseline jax {baseline.get('jax_version')}, running "
            f"{jax.__version__} — a compiler upgrade may legitimately "
            "shift counts; regenerate if every entry moved]"
        )
    entries = baseline.get("entries", {})
    for entry, recorded in sorted(census.items()):
        if "error" in recorded:
            findings.append(
                f"{entry}: census recording failed: {recorded['error']}"
            )
            continue
        pinned = entries.get(entry)
        if pinned is None:
            findings.append(
                f"{entry}: new jit entry not pinned in hlo_baseline.json — "
                "justify it with --update-baseline (and a reason)"
                + version_note
            )
            continue
        for metric in pinned.get("pinned", COUNT_METRICS):
            want = pinned.get("counts", {}).get(metric)
            got = recorded.get(metric)
            if want is None or got is None:
                continue
            if got != want:
                findings.append(
                    f"{entry}.{metric}: {got} vs baseline {want} "
                    f"(exact-match metric; a kernel was "
                    f"{'added' if got > want else 'removed'})" + version_note
                )
        rel = float(baseline.get("tolerance", {}).get("cost_rel", 0.10))
        for metric in ("flops", "bytes_accessed"):
            want = pinned.get(metric)
            got = recorded.get(metric)
            if not want or got is None:
                continue
            if abs(got - want) > rel * abs(want):
                findings.append(
                    f"{entry}.{metric}: {got:.0f} vs baseline {want:.0f} "
                    f"(outside ±{rel:.0%} tolerance)" + version_note
                )
    for entry in sorted(entries):
        if entry not in census:
            findings.append(
                f"baseline entry {entry!r} was not exercised by the census "
                "scenario (stale — remove it, or fix the scenario)"
            )
    return findings


def update_baseline(census: Dict[str, Dict], path: Optional[str] = None) -> str:
    from bcg_tpu.obs.hlo import COUNT_METRICS

    import jax

    path = path or baseline_path()
    prior = load_baseline(path) or {}
    prior_entries = prior.get("entries", {})
    entries = {}
    for entry, recorded in sorted(census.items()):
        if "error" in recorded:
            continue
        entries[entry] = {
            "reason": prior_entries.get(entry, {}).get(
                "reason",
                "pinned by scripts/hlo_census.py --update-baseline; "
                "justify intentional kernel-count changes here",
            ),
            "counts": {
                m: recorded[m] for m in COUNT_METRICS if m in recorded
            },
        }
        if "pinned" in recorded:
            # Pinned by part of the counts only (the scan arm's).
            entries[entry]["pinned"] = recorded["pinned"]
        for metric in ("flops", "bytes_accessed"):
            if metric in recorded:
                entries[entry][metric] = recorded[metric]
    data = {
        "_comment": (
            "HLO kernel-census baseline (scripts/hlo_census.py). Count "
            "metrics are exact-match on this backend: a change that adds "
            "a kernel to any pinned jit entry fails tier-1 "
            "(tests/test_hlo_census.py) until re-justified here via "
            "--update-baseline. flops/bytes_accessed carry a relative "
            "tolerance (tolerance.cost_rel)."
        ),
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "tolerance": prior.get("tolerance", {"cost_rel": 0.10}),
        "entries": entries,
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")
    return path


# ------------------------------------------------------------------ render
def render_table(census: Dict[str, Dict]) -> str:
    cols = ("fusions", "custom_calls", "collectives", "scatters", "gathers",
            "step_ops", "step_fusions", "total_ops")
    lines = []
    name_w = max([len("entry")] + [len(e) for e in census])
    header = f"{'entry':<{name_w}}  " + "  ".join(f"{c:>12}" for c in cols) \
        + f"  {'flops':>14}  {'bytes_acc':>14}"
    lines.append(header)
    for entry, rec in sorted(census.items()):
        if "error" in rec:
            lines.append(f"{entry:<{name_w}}  census failed: {rec['error']}")
            continue
        row = f"{entry:<{name_w}}  " + "  ".join(
            f"{rec.get(c, '-'):>12}" for c in cols
        )
        row += f"  {rec.get('flops', 0):>14.0f}  {rec.get('bytes_accessed', 0):>14.0f}"
        lines.append(row)
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Lowered-HLO kernel census per engine jit entry "
        "(hermetic CPU scenario)."
    )
    parser.add_argument("--check", action="store_true",
                        help="compare against hlo_baseline.json; rc 2 on drift")
    parser.add_argument("--update-baseline", action="store_true",
                        help="regenerate hlo_baseline.json (keeps reasons)")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="print the census as JSON")
    parser.add_argument("--arms", default=",".join(ARMS),
                        help=f"decode-loop families to exercise ({','.join(ARMS)})")
    args = parser.parse_args(argv)

    arms = tuple(a for a in args.arms.split(",") if a)
    bad = [a for a in arms if a not in ARMS]
    if bad:
        print(f"unknown arms {bad}; known: {ARMS}", file=sys.stderr)
        return 1
    census = run_scenario(arms)
    if args.as_json:
        print(json.dumps(census, indent=2, sort_keys=True))
    else:
        print(render_table(census))
    if args.update_baseline:
        path = update_baseline(census)
        print(f"baseline written: {path}", file=sys.stderr)
        return 0
    if args.check:
        findings = check_drift(census, load_baseline())
        for f in findings:
            print(f"DRIFT: {f}", file=sys.stderr)
        if findings:
            return 2
        print("hlo census matches baseline", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
