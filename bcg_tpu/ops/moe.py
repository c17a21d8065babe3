"""Routed experts: a layer that holds some of the experts its router
spans and computes their part of the result.

The router scores every published expert (``sigmoid(x W_r)``, float32),
each token takes its top ``k``, and the chosen scores, renormalised to
sum 1 where the model says so and times its scaling factor, weight the
experts' outputs.  An expert-parallel deployment puts each expert on one
of several chips; this layer is told which contiguous range it holds
(``first``, and as many as its stacks have) and adds up only those
experts' outputs.  It has no exchange with other chips and nothing
stands in for the experts it does not hold.

Each expert is a SwiGLU, ``(silu(x W_gate) * (x W_up)) W_down``.  With
int8 stacks (``{"q": int8 [E, in, out], "scale": f32 [E, out]}``, one
absmax scale per expert and output channel) the arithmetic is W8A8, as
``quantize.dense``'s: each row of the input quantised by its absmax, the
products summed in int32 and rescaled in float32, and the hidden row
``silu(g) * u`` quantised again before ``W_down``.

Three forms, one arithmetic, chosen by the caller (the engine resolves
it at boot; nothing here looks at the backend):

* :data:`XLA` — every held expert over every token, weighted by the
  routing (zero where a token did not choose the expert): exact, and
  the form for the CPU and for plain (unquantised) weights.
* the ``moe_prefill`` kernels (:func:`_grouped`), for many tokens: the
  (token, held expert) assignments sorted by expert, each expert's rows
  padded to a tile of :data:`ROWS`, and two grouped int8 matmuls (gate
  and up, quantising the hidden row; down, times the routing weight)
  whose grid walks the tiles, the tile's expert read from a
  scalar-prefetched table.  Tiles past the last assignment fetch and
  compute nothing.
* the ``moe_decode`` kernel (:func:`_decode`), for a decode step's few
  rows: a grid over the held experts in which only the experts the
  step's rows chose are streamed (a scalar-prefetched list of them), each
  once for all rows.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PALLAS = "pallas"
PALLAS_INTERPRET = "pallas_interpret"   # the kernels interpreted (tests)
XLA = "xla"

ROWS = 128          # rows of a grouped-matmul tile (one expert per tile)
_INT8_ROWS = 32     # int8 tiles are (32, 128): a decode step's rows pad to it
# the decode kernel holds one expert's three matrices, double-buffered
_DECODE_VMEM = 64 * 1024 * 1024
_PREFILL_VMEM = 48 * 1024 * 1024
_DOWN_BLOCK = 2048  # output columns of a W_down block


class Routing(NamedTuple):
    """A token's chosen experts (ids of the router's whole span) and
    their weights: ``experts`` int32 [N, k], ``weights`` float32 [N, k]."""
    experts: jax.Array
    weights: jax.Array


def route(h, router, k: int, normalize: bool, scaling: float) -> Routing:
    """``h`` [N, D]; ``router`` [D, E] (bfloat16, not quantised).  Sigmoid
    scores over all E experts, the top ``k``, renormalised to sum 1 with
    ``normalize``, times ``scaling``."""
    scores = jax.nn.sigmoid(jnp.dot(h, router, preferred_element_type=jnp.float32))
    top, experts = jax.lax.top_k(scores, k)
    if normalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return Routing(experts.astype(jnp.int32), top * scaling)


def held_weights(routing: Routing, first: int, held: int):
    """``[N, held]``: each token's weight on each held expert (0 where it
    did not choose it), and ``[N, held]`` bool, whether it chose it."""
    one = routing.experts[..., None] == first + jnp.arange(held)      # [N, k, held]
    return (jnp.sum(jnp.where(one, routing.weights[..., None], 0.0), axis=1),
            jnp.any(one, axis=1))


def experts_hit(routing: Routing, first: int, held: int):
    """How many distinct held experts the rows of ``routing`` chose."""
    return jnp.sum(jnp.any(held_weights(routing, first, held)[1], axis=0)).astype(jnp.int32)


def _quantize_rows(x):
    """Per-row absmax int8 and its float32 scale ``[..., 1]``."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1, keepdims=True), 1e-12) / 127.0
    return jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8), scale


def _int8_dot(xq, wq):
    return jax.lax.dot_general(xq, wq, (((xq.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32).astype(jnp.float32)


def _xla(h, cw, gate, up, down):
    """Every held expert over every token, weighted by ``cw`` [N, held]."""
    if isinstance(gate, dict):
        xq, xs = _quantize_rows(h)

        def expert(e):
            g = _int8_dot(xq, gate["q"][e]) * xs * gate["scale"][e]
            u = _int8_dot(xq, up["q"][e]) * xs * up["scale"][e]
            hq, hs = _quantize_rows(jax.nn.silu(g) * u)
            return _int8_dot(hq, down["q"][e]) * hs * down["scale"][e]
    else:
        x = h.astype(jnp.float32)

        def expert(e):
            g = jnp.dot(x, gate[e].astype(jnp.float32))
            u = jnp.dot(x, up[e].astype(jnp.float32))
            return jnp.dot(jax.nn.silu(g) * u, down[e].astype(jnp.float32))

    return sum(cw[:, e:e + 1] * expert(e) for e in range(cw.shape[1]))


# ------------------------------------------------------------- prefill

def _gate_up_kernel(tile_expert, live, layer, x_ref, xs_ref, g_ref, gs_ref, u_ref, us_ref,
                    hq_ref, hs_ref):
    """One tile's hidden rows ``silu(x W_gate) * (x W_up)``, quantised
    per row for ``W_down``; a tile past the last live one computes
    nothing (its blocks are the last live tile's: nothing is fetched)."""
    @pl.when(pl.program_id(0) < live[0])
    def _compute():
        x, xs = x_ref[...], xs_ref[...]
        g = _int8_dot(x, g_ref[0, 0]) * xs * gs_ref[0, 0]
        u = _int8_dot(x, u_ref[0, 0]) * xs * us_ref[0, 0]
        hq_ref[...], hs_ref[...] = _quantize_rows(jax.nn.silu(g) * u)


def _down_kernel(tile_expert, live, layer, h_ref, hs_ref, w_ref, d_ref, ds_ref, o_ref):
    """One (tile, output block) of ``W_down``, each row times its
    routing weight."""
    @pl.when(pl.program_id(0) < live[0])
    def _compute():
        o_ref[...] = _int8_dot(h_ref[...], d_ref[0, 0]) * (hs_ref[...] * w_ref[...]) * ds_ref[0, 0]


def _grouped(xq, xs, row_weight, gate, up, down, tile_expert, live, layer, interpret):
    """The grouped int8 SwiGLU over rows grouped by expert: ``xq`` int8
    [M, D] (M a multiple of ROWS, each expert's group padded to whole
    tiles), ``xs`` and ``row_weight`` [M, 1]; the stacks of every layer
    ``[L, E, in, out]``.  Tile t multiplies by expert ``tile_expert[t]``
    of layer ``layer[0]``.  Returns float32 [M, D], each row times its
    weight; rows of tiles past the last live one are left unwritten."""
    M, D = xq.shape
    F = gate["q"].shape[-1]
    bn = D if D % _DOWN_BLOCK else _DOWN_BLOCK
    tiles, nj = M // ROWS, D // bn

    def tile(t, live):
        # a tile past the last live one takes the last live tile's blocks
        return jnp.minimum(t, live[0] - 1)

    def column(t, j, live):
        return jnp.where(t >= live[0], nj - 1, j)

    # gate and up: grid (tiles,), one block of all F columns
    def gu_rows(t, te, live, layer):
        return tile(t, live), 0

    def gu_mat(t, te, live, layer):
        return layer[0], te[tile(t, live)], 0, 0

    # down: grid (tiles, nj)
    def rows(t, j, te, live, layer):
        return tile(t, live), 0

    def mat(t, j, te, live, layer):
        return layer[0], te[tile(t, live)], 0, column(t, j, live)

    def out(t, j, te, live, layer):
        return tile(t, live), column(t, j, live)

    scales = lambda w: w["scale"][:, :, None, :]  # noqa: E731
    params = lambda *dims: pltpu.CompilerParams(  # noqa: E731
        dimension_semantics=dims, vmem_limit_bytes=_PREFILL_VMEM)
    hq, hs = pl.pallas_call(
        _gate_up_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(tiles,),
            in_specs=[pl.BlockSpec((ROWS, D), gu_rows), pl.BlockSpec((ROWS, 1), gu_rows),
                      pl.BlockSpec((1, 1, D, F), gu_mat), pl.BlockSpec((1, 1, 1, F), gu_mat),
                      pl.BlockSpec((1, 1, D, F), gu_mat), pl.BlockSpec((1, 1, 1, F), gu_mat)],
            out_specs=[pl.BlockSpec((ROWS, F), gu_rows), pl.BlockSpec((ROWS, 1), gu_rows)],
        ),
        out_shape=[jax.ShapeDtypeStruct((M, F), jnp.int8),
                   jax.ShapeDtypeStruct((M, 1), jnp.float32)],
        compiler_params=params("arbitrary"), interpret=interpret, name="moe_prefill",
    )(tile_expert, live, layer, xq, xs, gate["q"], scales(gate), up["q"], scales(up))
    return pl.pallas_call(
        _down_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(tiles, nj),
            in_specs=[pl.BlockSpec((ROWS, F), rows), pl.BlockSpec((ROWS, 1), rows),
                      pl.BlockSpec((ROWS, 1), rows),
                      pl.BlockSpec((1, 1, F, bn), mat), pl.BlockSpec((1, 1, 1, bn), mat)],
            out_specs=pl.BlockSpec((ROWS, bn), out),
        ),
        out_shape=jax.ShapeDtypeStruct((M, D), jnp.float32),
        compiler_params=params("arbitrary", "arbitrary"),
        interpret=interpret, name="moe_prefill",
    )(tile_expert, live, layer, hq, hs, row_weight, down["q"], scales(down))


def prefill_layout(cw, chosen, k: int):
    """The layout of the grouped matmul: ``(row_token, row_weight,
    tile_expert, live)``.  Each (token, held expert) assignment of
    ``chosen`` [N, held] gets a row, grouped by expert in token order,
    each group padded to whole tiles of ROWS; ``row_token`` is -1 on
    padding rows, ``tile_expert`` the expert of each tile, ``live`` how
    many tiles hold rows (at least 1).  The row count is static: every
    token choosing ``min(k, held)`` held experts (``k`` the router's
    experts per token), plus a tile of padding per expert."""
    N, held = cw.shape
    M = (-(-N * min(k, held) // ROWS) + held) * ROWS
    mine = chosen
    counts = jnp.sum(mine, axis=0).astype(jnp.int32)
    padded = (counts + ROWS - 1) // ROWS * ROWS
    start = jnp.cumsum(padded) - padded
    rank = jnp.cumsum(mine, axis=0).astype(jnp.int32) - 1     # [N, held]
    dest = jnp.where(mine, start[None, :] + rank, M).reshape(-1)
    token = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[:, None], (N, held)).reshape(-1)
    row_token = jnp.full((M,), -1, jnp.int32).at[dest].set(token, mode="drop")
    row_weight = jnp.zeros((M,), jnp.float32).at[dest].set(cw.reshape(-1), mode="drop")
    end = jnp.cumsum(padded)
    tile_expert = jnp.minimum(
        jnp.searchsorted(end, jnp.arange(M // ROWS, dtype=jnp.int32) * ROWS, side="right"),
        held - 1).astype(jnp.int32)
    live = jnp.maximum(end[-1] // ROWS, 1).reshape(1).astype(jnp.int32)
    return row_token, row_weight, tile_expert, live


def _prefill(h, cw, chosen, k, gate, up, down, layer, interpret):
    N = h.shape[0]
    row_token, row_weight, tile_expert, live = prefill_layout(cw, chosen, k)
    xq, xs = _quantize_rows(h)
    take = jnp.maximum(row_token, 0)
    y = _grouped(jnp.take(xq, take, axis=0), jnp.take(xs, take, axis=0),
                 row_weight[:, None], gate, up, down, tile_expert, live, layer, interpret)
    # padding rows, and rows of tiles past the last live one (never
    # written), go to no token
    return jnp.zeros((N, y.shape[1]), jnp.float32).at[
        jnp.where(row_token >= 0, row_token, N)].add(y, mode="drop")


# -------------------------------------------------------------- decode

def _decode_kernel(hit, n_hit, layer, x_ref, xs_ref, cw_ref, g_ref, gs_ref, u_ref, us_ref,
                   d_ref, ds_ref, o_ref):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s < n_hit[0])
    def _expert():
        x, xs = x_ref[...], xs_ref[...]
        g = _int8_dot(x, g_ref[0, 0]) * xs * gs_ref[0, 0]
        u = _int8_dot(x, u_ref[0, 0]) * xs * us_ref[0, 0]
        hq, hs = _quantize_rows(jax.nn.silu(g) * u)
        o_ref[...] += cw_ref[0] * (_int8_dot(hq, d_ref[0, 0]) * hs * ds_ref[0, 0])


def _decode(h, cw, chosen, gate, up, down, layer, interpret):
    """A few rows: stream only the held experts some row chose, of layer
    ``layer[0]`` of the stacks ``[L, E, in, out]``."""
    B, D = h.shape
    held = cw.shape[1]
    Bp = -(-B // _INT8_ROWS) * _INT8_ROWS
    xq, xs = _quantize_rows(jnp.pad(h, ((0, Bp - B), (0, 0))))
    cwt = jnp.pad(cw, ((0, Bp - B), (0, 0))).T[:, :, None]         # [held, Bp, 1]
    used = jnp.any(chosen, axis=0)
    n_hit = jnp.sum(used).astype(jnp.int32)
    # the hit experts in order, then the last of them again (no new fetch)
    order = jnp.argsort(~used, stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n_hit - 1, 0)]
    hit = jnp.where(jnp.arange(held) < n_hit, order, last)
    K, F = gate["q"].shape[2:]

    def at(s, hit, n, layer):
        return layer[0], hit[s], 0, 0

    def row_weights(s, hit, n, layer):
        return hit[s], 0, 0

    full = lambda s, hit, n, layer: (0, 0)  # noqa: E731
    out = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(held,),
            in_specs=[
                pl.BlockSpec((Bp, K), full), pl.BlockSpec((Bp, 1), full),
                pl.BlockSpec((1, Bp, 1), row_weights),
                pl.BlockSpec((1, 1, K, F), at), pl.BlockSpec((1, 1, 1, F), at),
                pl.BlockSpec((1, 1, K, F), at), pl.BlockSpec((1, 1, 1, F), at),
                pl.BlockSpec((1, 1, F, D), at), pl.BlockSpec((1, 1, 1, D), at),
            ],
            out_specs=pl.BlockSpec((Bp, D), full),
        ),
        out_shape=jax.ShapeDtypeStruct((Bp, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_DECODE_VMEM),
        interpret=interpret,
        name="moe_decode",
    )(hit, n_hit.reshape(1), layer, xq, xs, cwt,
      gate["q"], gate["scale"][:, :, None, :], up["q"], up["scale"][:, :, None, :],
      down["q"], down["scale"][:, :, None, :])
    return out[:B]


def routed_experts(h, routing: Routing, gate, up, down, first: int = 0,
                   valid: Optional[jax.Array] = None, decode: bool = False,
                   impl: str = XLA, layer: Optional[jax.Array] = None):
    """The held experts' part of the layer's output, float32 [N, D].

    ``h`` [N, D] the sublayer's (normed) input; ``gate``, ``up``,
    ``down`` the held experts' stacks (int8 dicts or plain arrays
    ``[held, in, out]``), expert ``first + e`` at index e, or, with
    ``layer`` (a traced index), the stacks of every layer ``[L, held,
    in, out]``: the kernels then read that layer's experts through their
    index maps and no layer's stack is copied out of them.  ``valid``
    [N] (False on pad positions: they take no expert's work) or None;
    ``decode`` says the rows are one decode step's (the kernel that
    streams only the experts hit), else a prefill's (the grouped
    matmul).  ``impl`` is the caller's resolved choice."""
    held = (gate["q"] if isinstance(gate, dict) else gate).shape[-3]
    cw, chosen = held_weights(routing, first, held)
    if valid is not None:
        cw = jnp.where(valid[:, None], cw, 0.0)
        chosen = chosen & valid[:, None]
    if impl == XLA:
        if layer is not None:
            gate, up, down = jax.tree.map(lambda a: a[layer], (gate, up, down))
        return _xla(h, cw, gate, up, down)
    if impl not in (PALLAS, PALLAS_INTERPRET):
        raise ValueError(f"routed_experts: unknown impl {impl!r}")
    if not isinstance(gate, dict):
        raise ValueError("routed_experts: the expert kernels take int8 stacks")
    if layer is None:       # one layer's stacks: a stack of one layer
        gate, up, down = jax.tree.map(lambda a: a[None], (gate, up, down))
        layer = 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    # the TPU interpreter, which also refuses a block past an array's end
    interpret = impl == PALLAS_INTERPRET and pltpu.InterpretParams(out_of_bounds_reads="raise")
    if decode:
        return _decode(h, cw, chosen, gate, up, down, layer, interpret)
    return _prefill(h, cw, chosen, routing.experts.shape[1], gate, up, down, layer,
                    interpret)
