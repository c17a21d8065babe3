"""The model's parts, as the device trace names them.

Every operation of the engine's programs carries, in its HLO metadata
(``op_name``), the stack of ``jax.named_scope`` names it was traced
under.  The model and the engine open one scope per part, named from
:data:`PARTS`; the profiler keeps each program's HLO, ``op_name``
included, beside the device events (on a TPU in the ``/host:metadata``
plane, keyed by instruction name), so device time can be read per part
without guessing from shapes.  A
scope costs nothing at run time: it lives only in the metadata.

An operation belongs to the INNERMOST ``bcg.*`` segment of its name
stack (:func:`part`): ``jit(loop)/bcg.sample/while/body/bcg.head/
bcg.layers/while/body/bcg.attn/dot_general`` is attention.  So
``bcg.layers`` holds only what the layer scan does outside the three
sublayers, ``bcg.head`` only what a model call does outside the layers,
and ``bcg.sample`` only what the decode loop does outside the model.
Scopes below a part (``bcg.ffn/router``, ``bcg.linear_mixer/scan``) are
free; nothing reads them.

| Part | What it holds |
| --- | --- |
| ``bcg.attn`` | a full-attention mixer: pre-norm, q/k/v, q/k norms, rope, the output gate, the cache write, the flash or int8 decode kernel, the output projection |
| ``bcg.linear_mixer`` | a gated delta-rule, KDA or Mamba mixer: projections, short conv, chunk kernel or one-token update, output norm and gate, and its state's slice from and write back to the layer scan's carry (the one-token update fuses into the write); a Mamba mixer's x-projection, its norms, the step size's projection and softplus (the discretisation) and the selective scan (``mamba_prefill`` or the one-token update) under the free sub-scope ``scan`` |
| ``bcg.ffn`` | SwiGLU or routed experts with the shared one, with its pre-norm |
| ``bcg.layers`` | the layer scan outside the sublayers: weight slices, carries |
| ``bcg.head`` | a model call outside the layers: embedding, rope tables, masks, final norm, logits |
| ``bcg.sample`` | the decode loop outside the model: guided mask, sampler, token bookkeeping, the loop's condition |

No jax import: the benchmark's trace readers and the tests read the
vocabulary without the program's dependencies.
"""

from __future__ import annotations

from typing import Optional

PREFIX = "bcg."
ATTN = "bcg.attn"
LINEAR_MIXER = "bcg.linear_mixer"
FFN = "bcg.ffn"
LAYERS = "bcg.layers"
HEAD = "bcg.head"
SAMPLE = "bcg.sample"
PARTS = (ATTN, LINEAR_MIXER, FFN, LAYERS, HEAD, SAMPLE)


def part(op_name: str) -> Optional[str]:
    """The innermost ``bcg.*`` segment of an ``op_name`` name stack, or
    None where the operation carries none."""
    for segment in reversed(op_name.split("/")):
        if segment.startswith(PREFIX):
            return segment
    return None
