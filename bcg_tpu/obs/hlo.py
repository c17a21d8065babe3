"""HLO kernel census: lowered-program introspection per jit entry.

ROADMAP item 5 names "kernel-count per decode step via lowered-HLO
inspection" as the acceptance instrument for any fusion work, and the
retrace counters only say *that* a program recompiled — not what it
compiled INTO.  This module closes that gap: when the census is enabled
(``BCG_TPU_HLO_CENSUS=1``, or programmatically via :func:`enable`),
``engine/jax_engine.py`` hands each jit entry point's FIRST call here
(:func:`maybe_record`), the already-traced arguments are lowered and
compiled once more through the AOT API, and the compiled module is
parsed into an op census:

* **kernel-launching computations only** — the entry computation plus
  everything reachable through ``body=``/``condition=``/
  ``branch_computations=`` references (a while body's ops run once per
  decode step).  Computations referenced via ``calls=`` (fusion
  internals) or ``to_apply=`` (reduction lambdas) are *inside* a kernel
  and excluded, so ``total_ops`` approximates dispatched kernels, not
  HLO instructions.
* **category counts** — fusions, custom-calls, collectives
  (all-reduce / all-gather / reduce-scatter / collective-permute /
  all-to-all), scatter/gather, dynamic-(update-)slice, dots, whiles;
  plus the same counts restricted to while BODIES (``step_ops`` etc. —
  the per-decode-step kernel count the ROADMAP wants pinned).
* **XLA cost analysis** — flops and bytes-accessed of the compiled
  module, when the backend exposes them.

Every census lands in the process-wide counter registry as gauges
(``engine.hlo.<entry>.<metric>``) so it rides bench JSON and the
Prometheus exposition for free, and in :data:`CENSUS` for structured
consumers (``scripts/hlo_census.py``, the drift check against
``hlo_baseline.json``).

Cost: one extra lower+compile per (entry, first call) — which is why
the census is OFF by default and meant for the hermetic CPU census
script and tier-1 drift test, not the serving hot path.  Recording
never raises: a backend without ``as_text``/``cost_analysis`` simply
yields a partial census.

jax is imported lazily inside :func:`maybe_record` so this module stays
loadable by flag-only consumers (the trace-report path).
"""

from __future__ import annotations

import re
import threading
from typing import Any, Dict, List, Optional, Tuple

from bcg_tpu.obs import counters as obs_counters
from bcg_tpu.runtime import envflags

_COLLECTIVES = {
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "all-reduce-start",
    "all-gather-start",
}
# Census metric names, in render order.  ``flops``/``bytes_accessed``
# ride separately (cost analysis, not op parsing).
COUNT_METRICS = (
    "total_ops", "fusions", "custom_calls", "collectives", "scatters",
    "gathers", "dynamic_slices", "dots", "whiles",
    "step_ops", "step_fusions", "step_dots", "step_collectives",
    "step_gathers", "step_custom_calls",
)

_comp_header_re = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(.*\)\s*->.*\{$")
# The result type is either a scalar/array type (no spaces) or a tuple
# "(f32[...], s32[])" — a plain \S+ match would skip every tuple-typed
# instruction (the while op itself, multi-output fusions).
_op_re = re.compile(
    r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*(?:\([^)]*\)|\S+)\s+([a-z][a-z0-9\-]*)\("
)
_ref_res = {
    "body": re.compile(r"body=%?([\w.\-]+)"),
    "condition": re.compile(r"condition=%?([\w.\-]+)"),
    "branch": re.compile(r"branch_computations=\{([^}]*)\}"),
}


def parse_computations(hlo_text: str) -> Tuple[Optional[str], Dict[str, List[str]]]:
    """(entry computation name, {computation: [opcode, ...]}) from HLO
    long-form text."""
    comps: Dict[str, List[str]] = {}
    entry = None
    cur: Optional[str] = None
    for line in hlo_text.splitlines():
        s = line.rstrip()
        m = _comp_header_re.match(s)
        if m:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
            continue
        if s == "}":
            cur = None
            continue
        if cur is None:
            continue
        m2 = _op_re.match(s)
        if m2:
            comps[cur].append(m2.group(1))
    return entry, comps


def _categorize(ops: List[str]) -> Dict[str, int]:
    return {
        "total_ops": len(ops),
        "fusions": sum(1 for o in ops if o == "fusion"),
        "custom_calls": sum(1 for o in ops if o == "custom-call"),
        "collectives": sum(1 for o in ops if o in _COLLECTIVES),
        "scatters": sum(1 for o in ops if o == "scatter"),
        "gathers": sum(1 for o in ops if o == "gather"),
        "dynamic_slices": sum(
            1 for o in ops if o in ("dynamic-slice", "dynamic-update-slice")
        ),
        "dots": sum(1 for o in ops if o in ("dot", "dot-general", "convolution")),
        "whiles": sum(1 for o in ops if o == "while"),
    }


def census_from_text(hlo_text: str) -> Dict[str, int]:
    """Op census over the KERNEL-LAUNCHING computations of one compiled
    module (see module docstring for the inclusion rule), with the
    ``step_*`` family restricted to while bodies."""
    entry, comps = parse_computations(hlo_text)
    body_names = set(_ref_res["body"].findall(hlo_text))
    cond_names = set(_ref_res["condition"].findall(hlo_text))
    branch_names = set()
    for group in _ref_res["branch"].findall(hlo_text):
        for name in group.split(","):
            branch_names.add(name.strip().lstrip("%"))
    launching = (
        ({entry} if entry else set()) | body_names | cond_names | branch_names
    )
    all_ops: List[str] = []
    step_ops: List[str] = []
    for name, ops in comps.items():
        if name not in launching:
            continue
        all_ops.extend(ops)
        if name in body_names:
            step_ops.extend(ops)
    census = _categorize(all_ops)
    census.update(_step_family(_categorize(step_ops)))
    return census


def _step_family(step: Dict[str, int]) -> Dict[str, int]:
    return {
        "step_ops": step["total_ops"],
        "step_fusions": step["fusions"],
        "step_dots": step["dots"],
        "step_collectives": step["collectives"],
        "step_gathers": step["gathers"],
        "step_custom_calls": step["custom_calls"],
    }


# --------------------------------------------------- stablehlo (TPU lowering)
# The compiled-HLO census above is post-fusion and backend-exact, but it
# can only be taken on the backend the process runs on.  The claims the
# Pallas paged-attention kernel makes are TPU claims — on CPU the kernel
# runs through the interpret-mode EMULATION, whose lowering machinery
# inflates op counts and proves nothing about the hardware program.
# jax can, however, cross-LOWER a traced program for the TPU platform on
# any host (Mosaic kernels serialize into ``tpu_custom_call`` at
# lowering time).  Lowering never reaches Mosaic — a kernel it accepts
# can still be refused by the compiler — and the compile needs no
# hardware either: the installed TPU compiler builds for a DESCRIBED
# chip (tests/test_tpu_compile.py), which is where the kernels are held
# to what the chip's compiler accepts.  This census only counts ops, so
# the fused-vs-gather comparison is taken on the TPU StableHLO lowering:
# both arms carry the identical transformer skeleton, and the attention
# inner region is the only difference — N gather/reshape/softmax ops per
# layer per step versus ONE fused kernel custom-call.  Pre-fusion op
# counts are not kernel counts, but at the same IR level with the same
# skeleton the strict inequality (and the per-layer attention gathers
# and dots vanishing from the step body in favor of one custom call per
# layer) is exactly the fusion claim, hermetically.

_mlir_op_re = re.compile(r'(?:=\s*|^\s*)"?stablehlo\.([a-z_0-9]+)"?[\s("]')


def census_from_stablehlo(text: str) -> Dict[str, int]:
    """Op census over a StableHLO (MLIR) module, with the ``step_*``
    family counting ops nested inside ``stablehlo.while`` regions.
    ``constant``/``return`` lines are excluded (materialization noise at
    this IR level); ``fusions`` is structurally 0 — StableHLO is
    pre-fusion, which is why entries recorded this way pin the
    comparison-bearing counts (gathers, custom calls, dots, step totals)
    rather than claiming kernel counts."""
    all_ops: List[str] = []
    step_ops: List[str] = []
    depth = 0
    # Active while ops: [region base depth, regions-opened flag].  An op
    # is in a step body iff it sits deeper than the OUTERMOST active
    # while; a while is popped once its regions opened and closed back
    # to base (`} do {` nets zero braces, so depth only returns to base
    # at the real end).
    stack: List[List] = []
    for line in text.splitlines():
        m = _mlir_op_re.search(line)
        if m:
            op = m.group(1).replace("_", "-")
            if op not in ("constant", "return"):
                all_ops.append(op)
                if stack and depth > stack[0][0]:
                    step_ops.append(op)
                if op == "while":
                    stack.append([depth, False])
        depth += line.count("{") - line.count("}")
        for entry in stack:
            if depth > entry[0]:
                entry[1] = True
        while stack and stack[-1][1] and depth <= stack[-1][0]:
            stack.pop()
    census = _categorize(all_ops)
    census.update(_step_family(_categorize(step_ops)))
    return census


def _cost_analysis(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
    except (TypeError, ValueError, AttributeError, NotImplementedError,
            RuntimeError, IndexError):
        # Backend without cost analysis (some TPU/PJRT paths raise
        # XlaRuntimeError/Unimplemented here) — census stays partial.
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out = {}
    if "flops" in ca:
        out["flops"] = float(ca["flops"])
    if "bytes accessed" in ca:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out


# --------------------------------------------------------------- recorder
# entry name -> census dict (counts + flops/bytes + backend).
CENSUS: Dict[str, Dict[str, Any]] = {}
_lock = threading.Lock()
_enabled: Optional[bool] = None  # tri-state: None = read the env flag


def enabled() -> bool:
    global _enabled
    if _enabled is None:
        _enabled = envflags.get_bool("BCG_TPU_HLO_CENSUS")
    return _enabled


def enable(on: bool = True) -> None:
    """Programmatic switch (``scripts/hlo_census.py``, tests) — wins
    over the env flag until :func:`reset`."""
    global _enabled
    _enabled = on


def reset() -> None:
    """Drop recorded censuses AND the cached enable flag — test/script
    use."""
    global _enabled
    with _lock:
        CENSUS.clear()
        _enabled = None


def maybe_record(entry: str, jitted, args: tuple, kwargs: Optional[dict] = None) -> None:
    """Record the census for ``entry`` from a jitted callable and the
    concrete arguments of a call the engine is ABOUT to make (first call
    per entry only; no-op when the census is disabled).

    Uses the AOT path (``jitted.lower(*args).compile()``) so the parsed
    module is exactly what this backend executes for these shapes.  The
    extra compile is paid once per entry and only in census mode; the
    jit's own execution cache is untouched, so enabling the census
    changes no shapes and provokes no retraces.
    """
    if not enabled() or entry in CENSUS:
        return
    with _lock:
        if entry in CENSUS:  # raced
            return
        census: Dict[str, Any] = {}
        try:
            import jax

            # Compile-cost accounting (BCG_TPU_COMPILE_OBS): the AOT
            # lower+compile below is a REAL extra compile this process
            # pays for the census — charged under the entry's
            # engine.compile_ms histogram + the cumulative aot_ms
            # counter (obs/compile.py; shared no-op when off).
            from bcg_tpu.obs import compile as obs_compile

            with obs_compile.measure_aot(entry):
                lowered = jitted.lower(*args, **(kwargs or {}))
                compiled = lowered.compile()
            census.update(census_from_text(compiled.as_text()))
            census.update(_cost_analysis(compiled))
            census["backend"] = jax.default_backend()
        except Exception as exc:
            # A census failure must never take the serving call down;
            # the partial record names the failure for the script/test.
            census["error"] = f"{type(exc).__name__}: {str(exc)[:200]}"
        CENSUS[entry] = census
    publish_gauges(entry, census)


def recorded(entry: str) -> bool:
    """True once ``entry`` has a census (callers can skip building the
    arguments for a record that would be a no-op)."""
    return entry in CENSUS


def record_tpu_lowering(entry: str, jitted, args: tuple,
                        kwargs: Optional[dict] = None) -> None:
    """Record a census of ``jitted``'s TPU cross-lowering (StableHLO)
    WITHOUT executing or compiling it — no hardware needed, and safe
    for programs (like the non-interpret Pallas paged loop) that could
    not run on this host at all.  The engine uses this to pin the
    fused-kernel-vs-gather comparison hermetically; see the
    stablehlo-census comment above.  First record per entry wins; a
    failure is contained as an error record like :func:`maybe_record`."""
    if not enabled() or entry in CENSUS:
        return
    with _lock:
        if entry in CENSUS:  # raced
            return
        census: Dict[str, Any] = {}
        try:
            traced = jitted.trace(*args, **(kwargs or {}))
            lowered = traced.lower(lowering_platforms=("tpu",))
            census.update(census_from_stablehlo(lowered.as_text()))
            census["backend"] = "stablehlo-tpu"
        except Exception as exc:
            census["error"] = f"{type(exc).__name__}: {str(exc)[:200]}"
        CENSUS[entry] = census
    publish_gauges(entry, census)


def wrap(entry: str, jitted):
    """Call-site shim: returns ``jitted`` unchanged unless the census is
    enabled and ``entry`` is still unrecorded, in which case the first
    call records the census (from the exact concrete arguments) before
    executing — so engine call sites pay ZERO overhead disabled and one
    AOT lower+compile per entry enabled."""
    if not enabled() or entry in CENSUS:
        return jitted

    def _recording_call(*args, **kwargs):
        maybe_record(entry, jitted, args, kwargs)
        return jitted(*args, **kwargs)

    return _recording_call


def publish_gauges(entry: str, census: Dict[str, Any]) -> None:
    """Mirror one census into registry gauges
    (``engine.hlo.<entry>.<metric>``) — the bench-JSON / Prometheus
    surface."""
    for metric in COUNT_METRICS + ("flops", "bytes_accessed"):
        value = census.get(metric)
        if value is not None:
            obs_counters.set_gauge(f"engine.hlo.{entry}.{metric}", value)


def snapshot() -> Dict[str, Dict[str, Any]]:
    """Copy of every recorded census (entry -> metrics)."""
    with _lock:
        return {k: dict(v) for k, v in CENSUS.items()}
