"""Pallas W4A16 matmul: grouped-int4 weights dequantized in VMEM.

int4 weights exist for CAPACITY (the reference's 14B preset on one
16 GB chip — its own guidance is "24GB+ VRAM" per README.md:33); this
kernel keeps them from costing 3x the HBM traffic they save.  The XLA
fallback (models/quantize.py dequantize_int4) materializes the bf16
weight in HBM every call — int4 read + bf16 write + bf16 read is ~2.5x
the bytes of just reading bf16.  Here each weight tile is dequantized
AFTER the DMA, in VMEM, so HBM sees only the packed int4 bytes: the
bandwidth-bound decode step streams half the bytes of int8, a quarter
of bf16.

Packing contract (models/quantize.py quantize_weight_int4): byte
``[i, f]`` of the packed [P, F] array (P = D/2) holds weight row ``i``
in its low nibble and row ``P + i`` in its high nibble.  Contraction is
a sum over rows, so the kernel never interleaves nibbles: it dots the
low-nibble tile against ``x[:, :P]`` and the high tile against
``x[:, P:]``.  Group scales are [D/g, F] bf16, groups running top half
then bottom half (g | P by construction).

Grid is (M blocks, F blocks) only — the contraction loop lives INSIDE
the kernel (fori over g-row groups) so per-program overhead (~2 us,
measured round 3 on the int8 decode kernels) is paid tens of times per
matmul, not hundreds: the q4 ref's block is a full [P, block_f] column
strip (2.5 MB VMEM at 14B shapes), not a [g, block_f] sliver.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _w4_kernel(x_ref, q4_ref, gs_ref, o_ref, *, group, num_groups):
    """One [block_m, block_f] output tile.

    x_ref: [block_m, D] bf16; q4_ref: [P, block_f] int8 (packed);
    gs_ref: [2P/g, block_f] bf16; o_ref: [block_m, block_f] f32.
    """
    P = q4_ref.shape[0]

    # STATIC Python unroll over g-row groups: the earlier fori_loop
    # carried a traced index into every slice, making them dynamic —
    # including 1-sublane-row bf16 slices of gs_ref, which an earlier
    # Mosaic crashed on at every real shape.  This static form compiles
    # for a described v5e under jaxlib 0.9.0 / libtpu 0.0.34 at every
    # 8B and 14B projection shape, M=10 and 40 (PR 22; compiled, not
    # yet run on a chip).  Static offsets (all multiples of the 128-row
    # group) lower cleanly; the
    # unrolled program is ~num_groups x 12 ops (<= ~900 at the 14B
    # w_down strip), well within Mosaic program limits, and the
    # in-kernel contraction still amortizes per-program overhead the
    # way the fori version did.
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(num_groups):
        packed = q4_ref[pl.ds(j * group, group), :]
        # int32 shifts sign-extend reliably on the VPU; int8 shift
        # lowering is spottier across Mosaic versions.
        p32 = packed.astype(jnp.int32)
        low = jnp.right_shift(jnp.left_shift(p32, 28), 28)
        high = jnp.right_shift(p32, 4)
        s_low = gs_ref[pl.ds(j, 1), :].astype(jnp.float32)
        s_high = gs_ref[pl.ds(num_groups + j, 1), :].astype(jnp.float32)
        w_low = (low.astype(jnp.float32) * s_low).astype(jnp.bfloat16)
        w_high = (high.astype(jnp.float32) * s_high).astype(jnp.bfloat16)
        x_low = x_ref[:, pl.ds(j * group, group)]
        x_high = x_ref[:, pl.ds(P + j * group, group)]
        acc = acc + jax.lax.dot_general(
            x_low, w_low, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = acc + jax.lax.dot_general(
            x_high, w_high, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    o_ref[...] = acc


def _row_block(M: int, block_m: int) -> int:
    """Actual row-block size for an M-row call: the requested block, or
    M rounded up to a sublane multiple when smaller.  Shared by
    :func:`w4a16_supported` and :func:`w4a16_matmul` so the supported
    check always budgets VMEM for the block size the call will use."""
    return block_m if M >= block_m else max(8, ((M + 7) // 8) * 8)


def _pick_block_f(P: int, F: int, block_m: int) -> int:
    # Budget the WHOLE working set inside ~14 MB of VMEM, double
    # buffering the streamed inputs: the packed [P, block_f] int8 strip,
    # the [block_m, D=2P] bf16 x block, the f32 output tile, and the
    # gscale sliver (negligible).  The x block is not free: at 14B
    # w_down shapes (P=8704, D=17408) a block_m=128 x block is 4.5 MB —
    # strip-only budgeting picked block_f=512 there and overflowed VMEM.
    x_bytes = 2 * (block_m * 2 * P * 2)
    for cand in (512, 256, 128):
        if F % cand:
            continue
        strip = 2 * (P * cand)
        out_b = block_m * cand * 4
        if x_bytes + strip + out_b <= 14 * 1024 * 1024:
            return cand
    return 0


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _w4a16_2d(x, q4, gscale, block_m: int, interpret: bool):
    M, D = x.shape
    P, F = q4.shape
    num_groups = gscale.shape[0] // 2
    group = P // num_groups
    block_f = _pick_block_f(P, F, block_m)
    Mp = ((M + block_m - 1) // block_m) * block_m
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_w4_kernel, group=group, num_groups=num_groups),
        grid=(Mp // block_m, F // block_f),
        in_specs=[
            pl.BlockSpec((block_m, D), lambda m, f: (m, 0)),
            pl.BlockSpec((P, block_f), lambda m, f: (0, f)),
            pl.BlockSpec((2 * num_groups, block_f), lambda m, f: (0, f)),
        ],
        out_specs=pl.BlockSpec((block_m, block_f), lambda m, f: (m, f)),
        out_shape=jax.ShapeDtypeStruct((Mp, F), jnp.float32),
        interpret=interpret,
    )(x.astype(jnp.bfloat16), q4, gscale)
    return out[:M]


def w4a16_supported(x_shape, q4_shape, gscale_shape, block_m: int = 128) -> bool:
    """Static shape check used by :func:`w4a16_matmul` before invoking
    the kernel (``dense()`` gates only on row count / backend / device
    count and relies on this internal fallback): the kernel needs g | P,
    a lane-aligned F, and a working set that fits VMEM at the row-block
    size the call will actually use."""
    D = x_shape[-1]
    P, F = q4_shape
    if D != 2 * P or gscale_shape[0] % 2 or gscale_shape[1] != F:
        return False
    num_groups = gscale_shape[0] // 2
    if num_groups == 0 or P % num_groups:
        return False
    group = P // num_groups
    if group % 128 and group != P:  # sublane-friendly groups
        return False
    return _pick_block_f(P, F, _row_block(x_shape[0], block_m)) != 0


def w4a16_matmul(x, q4, gscale, block_m: int = 128, interpret: bool = False):
    """``x @ dequant(q4, gscale)`` with in-VMEM dequantization.

    x: [..., D] (any leading dims); q4: [D/2, F] packed int4;
    gscale: [D/g, F] bf16.  Returns [..., F] f32 (callers cast).
    Falls back to the XLA dequant path when shapes don't fit the kernel
    contract (w4a16_supported).
    """
    lead = x.shape[:-1]
    M = 1
    for s in lead:
        M *= s
    x2 = x.reshape(M, x.shape[-1])
    # Shapes outside the kernel's contract take the XLA dequant path.
    # The backend is the caller's business (``quantize.dense`` picks the
    # kernel only on a single TPU device): a direct call off-TPU without
    # ``interpret`` fails to lower rather than quietly running XLA.
    if not w4a16_supported(x2.shape, q4.shape, gscale.shape, block_m):
        from bcg_tpu.models.quantize import dequantize_int4

        w = dequantize_int4({"q4": q4, "gscale": gscale})
        return (x2.astype(jnp.bfloat16) @ w).astype(jnp.float32).reshape(*lead, -1)
    out = _w4a16_2d(x2, q4, gscale, _row_block(M, block_m), interpret)
    return out.reshape(*lead, q4.shape[1])
