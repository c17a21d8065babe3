"""Every device operation of the engine's programs names the model part
it belongs to (``bcg_tpu/obs/scopes.py``): the tiny dense, hybrid,
Solar and Jamba engines under their cells' options, their chunk program and
decode loop compiled on the CPU and read as the device trace reads
them, by the innermost ``bcg.*`` segment of each operation's
``op_name``.  ``tests/test_tpu_compile.py::TestKernelParts`` holds the
Pallas kernels, which run only on the chip, to their parts."""

import json
import os
import re
import sys

import pytest

from bcg_tpu.obs import hlo as obs_hlo
from bcg_tpu.obs import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from lib import scopes as lib_scopes  # noqa: E402

CELLS = ("qwen3-8b-int8", "olmo-hybrid-7b-int8", "solar-open2-250b-int8", "jamba2-3b-bf16")
KERNEL_OPS = ("fusion", "custom-call", "dot", "convolution")

# instruction name and opcode
_OP = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(?:\([^)]*\)|\S+)\s+([a-z][a-z0-9\-]*)\(")


def parts_of(text: str) -> dict:
    """``{kind: [(computation, op), ...]}`` of the fusions, custom calls,
    dots and convolutions of a compiled program's kernel-launching
    computations (``obs/hlo.py::census_from_text``'s rule), ``kind``
    their part, ``"unscoped"`` where the program traced the operation
    outside every part, or ``"compiler"`` where nothing of it carries an
    ``op_name``: an operation the compiler made (a CPU upcast of a
    bfloat16 parameter, a folded constant; a parameter's ``op_name`` is
    its argument's name, no scope stack).  An operation's ``op_name`` is
    found as the device trace's reader finds it
    (``benchmark/lib/scopes.py::op_names``)."""
    names = lib_scopes.op_names(text)
    comps, current = {}, None
    for line in text.splitlines():
        header = obs_hlo._comp_header_re.match(line.rstrip())
        if header:
            current = header.group(2)
            comps[current] = {"entry": bool(header.group(1)), "ops": {}}
            continue
        m = _OP.match(line) if current else None
        if m:
            comps[current]["ops"][m.group(1)] = m.group(2)
    entry = next(name for name, c in comps.items() if c["entry"])
    loops = (set(obs_hlo._ref_res["body"].findall(text))
             | set(obs_hlo._ref_res["condition"].findall(text)))
    out = {}
    for comp in {entry} | loops:
        for name, opcode in comps[comp]["ops"].items():
            if opcode in KERNEL_OPS:
                op_name = names[name]
                kind = "compiler" if op_name is None else scopes.part(op_name) or "unscoped"
                out.setdefault(kind, []).append((comp, name))
    out["entry"] = entry
    return out


@pytest.fixture(scope="module")
def programs():
    return {}


def _compiled(cell, options, programs, monkeypatch):
    """``{entry: compiled text}`` of the chunk program and the decode
    loop of one guided call on the cell's tiny engine, as the census
    would compile them from the call's own arguments."""
    if cell in programs:
        return programs[cell]
    from bcg_tpu.config import EngineConfig
    from bcg_tpu.engine.jax_engine import JaxEngine

    texts = {}

    def capture(entry, jitted, args, kwargs=None):
        if entry in ("prefill_chunk", "decode_loop") and entry not in texts:
            texts[entry] = jitted.lower(*args, **(kwargs or {})).compile().as_text()

    monkeypatch.setattr(obs_hlo, "maybe_record", capture)
    monkeypatch.setattr(obs_hlo, "record_tpu_lowering", lambda *a, **k: None)
    obs_hlo.enable(True)
    schema = {"type": "object", "properties": {"v": {"type": "integer"}},
              "required": ["v"]}
    engine = JaxEngine(EngineConfig(backend="jax", max_model_len=1024, **options(cell)))
    try:
        engine.batch_generate_json(
            [("system " * 20, "a prompt " * (10 + 3 * i), schema) for i in range(3)],
            temperature=0.0, max_tokens=12)
    finally:
        engine.shutdown()
        obs_hlo.reset()
    assert set(texts) == {"prefill_chunk", "decode_loop"}
    programs[cell] = texts
    return texts


@pytest.mark.parametrize("cell", CELLS)
def test_operations_carry_their_part(cell, cell_engine_options, programs, monkeypatch):
    """At least 98% of the operations the programs traced carry a part
    of the vocabulary; the ones the compiler made run once a program
    call, outside every loop."""
    for entry, text in _compiled(cell, cell_engine_options, programs, monkeypatch).items():
        kinds = parts_of(text)
        made = kinds.pop("compiler", [])
        entry_comp = kinds.pop("entry")
        assert set(kinds) - {"unscoped"} <= set(scopes.PARTS), (entry, set(kinds))
        traced = sum(len(v) for v in kinds.values())
        assert len(kinds.get("unscoped", ())) <= 0.02 * traced, (entry, kinds.get("unscoped"))
        assert all(comp == entry_comp for comp, _ in made), (entry, made)
        assert len(made) <= 0.05 * (traced + len(made)), (entry, made)


@pytest.mark.parametrize("cell", CELLS)
def test_each_program_holds_its_parts(cell, cell_engine_options, programs, monkeypatch):
    """The parts each program is made of: a chunk program has no
    sampler, a decode loop has one; a hybrid's layers have linear
    mixers."""
    texts = _compiled(cell, cell_engine_options, programs, monkeypatch)
    model = {scopes.HEAD, scopes.LAYERS, scopes.ATTN, scopes.FFN}
    if cell != "qwen3-8b-int8":
        model.add(scopes.LINEAR_MIXER)
    for entry, text in texts.items():
        kinds = parts_of(text)
        held = set(kinds) & set(scopes.PARTS)
        assert held == (model | {scopes.SAMPLE} if entry == "decode_loop" else model), entry


@pytest.mark.parametrize("cell", CELLS[1:])
def test_recurrent_state_goes_back_under_its_mixer(cell, cell_engine_options, programs,
                                                    monkeypatch):
    """The layer scan writes no state back as its own work: a recurrent
    layer's state leaves the carry and returns to it under
    ``bcg.linear_mixer`` (on the chip its one-token update fuses into
    that write), K/V is written inside ``bcg.attn``."""
    for entry, text in _compiled(cell, cell_engine_options, programs, monkeypatch).items():
        names = lib_scopes.op_names(text)
        writes = {scopes.part(names[m.group(1)] or "") for m in map(_OP.match, text.splitlines())
                  if m and m.group(2) == "dynamic-update-slice"}
        assert scopes.LINEAR_MIXER in writes and scopes.LAYERS not in writes, (entry, writes)


def test_innermost_part_wins():
    assert scopes.part("jit(loop)/bcg.sample/while/body/bcg.head/bcg.layers/while/body/"
                       "bcg.attn/dot_general") == scopes.ATTN
    assert scopes.part("jit(loop)/bcg.sample/while/body/add") == scopes.SAMPLE
    assert scopes.part("jit(prefill_chunk)/bcg.head/bcg.ffn/router/top_k") == scopes.FFN
    assert scopes.part("params['embed']") is None


def test_the_benchmark_reads_parts_of_the_vocabulary():
    """The scope metrics' files name parts of :data:`scopes.PARTS`, and
    between them every part."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    named = set()
    for m in bench["per_layer"]:
        with open(os.path.join(ROOT, "benchmark", "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        if spec["reader"] == "scope_seconds" and spec["args"]["scopes"]:
            named |= set(spec["args"]["scopes"])
    assert named == set(scopes.PARTS)
