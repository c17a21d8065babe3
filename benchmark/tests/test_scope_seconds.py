"""The device seconds by model part (``lib/scopes.py``,
``readers/scope_seconds.py``) on a small hand-made record: the
innermost ``bcg.*`` segment of an operation's name stack takes its
time, a program without the scopes reads null, and the parts with the
unscoped time add up to the window's operation seconds as
``lib/trace.py`` sums them.  The ``op_name`` lookup in a program's HLO
is checked on a trace of a scoped program made here on the CPU."""

import glob
import os

import pytest

from lib import scopes, trace
from readers import scope_seconds

WINDOW = (1000.0, 11000.0)
# [event name (HLO text), start_ns, dur_ns, op_name]
ROWS = [
    ["%fusion.1 = bf16[10,4096] fusion(%p)", 1000.0, 2000.0,
     "jit(prefill_chunk)/bcg.head/bcg.layers/while/body/closed_call/bcg.attn/dot_general"],
    ["%fusion.2 = bf16[10,4096] fusion(%p)", 3000.0, 1000.0,
     "jit(prefill_chunk)/bcg.head/bcg.layers/while/body/dynamic_slice"],
    ["%fusion.3 = f32[10,12288] fusion(%p)", 4000.0, 1500.0,
     "jit(loop)/bcg.sample/while/body/bcg.head/bcg.layers/while/body/bcg.ffn/router/top_k"],
    ["%while.4 = (s32[]) while(%t), body=%b", 1000.0, 9000.0, "jit(loop)/bcg.sample/while"],
    ["%gated_delta_prefill.5 = f32[10,30,512,192] custom-call(%q)", 5500.0, 1500.0,
     "jit(prefill_chunk)/bcg.head/bcg.layers/bcg.linear_mixer/gated_delta_prefill/pallas_call"],
    ["%fusion.6 = f32[10] fusion(%p)", 7000.0, 500.0, "jit(loop)/bcg.sample/while/body/add"],
    ["%copy.7 = s8[10,4096] copy(%p)", 7500.0, 500.0, None],
    ["%fusion.8 = bf16[10,1,4096] fusion(%p)", 8000.0, 1000.0,
     "jit(loop)/bcg.sample/while/body/bcg.head/embed"],
    ["%fusion.9 = f32[10] fusion(%p)", 10500.0, 1000.0, "jit(loop)/bcg.sample/argmax"],
    ["%fusion.10 = f32[10] fusion(%p)", 500.0, 400.0, "jit(loop)/bcg.sample/argmax"],
]
NS = 1e-9


def ctx(rounds=2):
    return {"window": {"game_rounds": rounds}}


@pytest.fixture
def record(monkeypatch):
    monkeypatch.setattr(scopes, "SOURCE", lambda c: scopes.table(ROWS, WINDOW))


def test_the_innermost_part_takes_the_time(record):
    table = scopes.seconds(ctx())
    assert table == pytest.approx({
        "bcg.attn": 2000 * NS, "bcg.layers": 1000 * NS, "bcg.ffn": 1500 * NS,
        "bcg.linear_mixer": 1500 * NS, "bcg.sample": (500 + 500) * NS,
        "bcg.head": 1000 * NS, None: 500 * NS})
    assert scope_seconds.read(ctx(), ["bcg.attn"]) == pytest.approx(1000 * NS)
    assert scope_seconds.read(ctx(), ["bcg.ffn", "bcg.layers"]) == pytest.approx(1250 * NS)
    assert scope_seconds.read(ctx(), ["bcg.nothing"]) == 0.0


def test_the_unscoped_share(record):
    assert scope_seconds.read(ctx(), None) == pytest.approx(500 / 8500)


def test_parts_add_up_to_the_window_operation_seconds():
    """The shell (the while) and what lies outside the window are left
    out as ``lib/trace.py`` leaves them out of its by-name sums."""
    rows = [["/device:TPU:0", trace.OPS_LINE, trace.op_name(n), s, d] for n, s, d, _ in ROWS]
    rows.append(["/host:CPU", "python", trace.ROUND_SPAN, WINDOW[0], WINDOW[1] - WINDOW[0]])
    reduced = trace.reduce_events(rows)
    assert sum(scopes.table(ROWS, WINDOW).values()) == pytest.approx(
        sum(reduced["ops_s"].values()))


def test_a_program_without_scopes_reads_null(monkeypatch):
    bare = [[n, s, d, o and o.replace("bcg.", "x.")] for n, s, d, o in ROWS]
    monkeypatch.setattr(scopes, "SOURCE", lambda c: scopes.table(bare, WINDOW))
    assert scopes.seconds(ctx()) is None
    assert scope_seconds.read(ctx(), ["bcg.attn"]) is None
    assert scope_seconds.read(ctx(), None) is None


def test_no_trace_reads_null(monkeypatch):
    monkeypatch.setattr(scopes, "SOURCE", lambda c: None)
    assert scope_seconds.read(ctx(), None) is None


COMPILED = """\
HloModule jit_f, entry_computation_layout={(f32[4,8]{1,0})->f32[2,8]{1,0}}

%wrapped_slice_computation (param_0: f32[4,8]) -> f32[2,8] {
  %param_0 = f32[4,8]{1,0} parameter(0)
  ROOT %slice.1 = f32[2,8]{1,0} slice(%param_0), slice={[0:2], [0:8]}, \
metadata={op_name="jit(f)/bcg.head/bcg.layers/while/body/dynamic_slice"}
}

ENTRY %main.5 (p: f32[4,8]) -> f32[2,8] {
  %p = f32[4,8]{1,0} parameter(0)
  %copy.2 = f32[4,8]{0,1} copy(%p)
  %slice-start = ((f32[4,8]{0,1}), f32[2,8]{1,0}, s32[]) async-start(%copy.2), \
calls=%wrapped_slice_computation
  %slice-done = f32[2,8]{1,0} async-done(%slice-start)
  ROOT %tanh.3 = f32[2,8]{1,0} tanh(%slice-done), metadata={op_name="jit(f)/bcg.head/bcg.ffn/tanh"}
}
"""


def test_compiler_made_operations_take_what_they_wrap():
    """An async slice's start and done carry no op_name of their own:
    they take the slice's, which the compiler wrapped; a layout copy of
    a parameter reaches none."""
    names = scopes.op_names(COMPILED)
    layers = "jit(f)/bcg.head/bcg.layers/while/body/dynamic_slice"
    assert names["slice-done"] == names["slice-start"] == layers
    assert names["tanh.3"] == "jit(f)/bcg.head/bcg.ffn/tanh"
    assert names["copy.2"] is None and names["p"] is None


def test_op_names_from_the_programs_hlo(tmp_path):
    """A CPU trace keeps each program's HLO on its metadata plane, as a
    TPU trace does: the operations' ``op_name`` stacks are read from it
    by instruction name."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("bcg.layers"):
            def body(h, _):
                with jax.named_scope("bcg.ffn"):
                    return jnp.tanh(h @ h), None
            h, _ = jax.lax.scan(body, x, None, length=2)
        with jax.named_scope("bcg.head"):
            return jnp.sum(h)

    fn = jax.jit(f)
    x = jnp.ones((8, 8))
    fn(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    fn(x).block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    protos = scopes.hlo_protos(path)
    module = next(k for k in protos if k.startswith("jit_f("))
    parts = {scopes.part(n) for n in scopes.hlo_op_names(protos[module]).values()}
    assert {"bcg.layers", "bcg.ffn", "bcg.head"} <= parts
