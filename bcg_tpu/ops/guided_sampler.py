"""Fused guided-sampling Pallas kernel: the whole masked-sampler
pipeline in one device program per row.

Every decode-loop iteration runs the guided sampler
(``engine/speculative.py make_masked_sampler``): DFA allowed-mask
(a ``min_budget`` row gather), EOS gate, temperature scaling, top-p
nucleus filter (a full ``[B, V]`` sort + cumsum on the XLA path),
categorical/argmax draw, and the DFA transition.  XLA lowers that as
several kernels with ``[B, V]`` intermediates materialized per step —
measurable step-op weight in the HLO census (``decode_loop``
step_fusions) and real HBM traffic at 150k-token vocabularies.  This
module moves the ``[B, V]``-shaped part of the pipeline into ONE Pallas
kernel:

* **grid over rows** — one program per batch row; the row's vocab lives
  in VMEM for the whole program (the ``[B, V]`` arrays are reshaped to
  ``[B, V/128, 128]`` so Mosaic tiles them densely; every preset vocab
  is already a multiple of the 128-lane width).
* **scalar-prefetch DFA indexing** — ``dfa_ids`` and the clamped DFA
  states ride as scalar-prefetch operands, so each row's
  ``min_budget[dfa, state]`` slice is DMA'd straight from HBM by the
  BlockSpec index map (the same trick the paged-attention kernel plays
  with its block table); the ``[B, V]`` mask gather never materializes.
* **top-p via a threshold scan instead of a full sort** — pass 1
  computes the row's masked-softmax stats (max, normalizer); pass 2
  finds the nucleus cutoff by bisecting the mass function
  ``mass(t) = sum of exp(x - M) over x - M >= t`` over the log-prob
  range: ~30 cheap in-VMEM reductions converge the threshold to float
  precision, where the XLA reference pays a ``[B, V]`` sort + cumsum.
  The kept set equals the reference nucleus unless two distinct token
  probabilities straddle the cutoff within ~1e-7 relative (ties at the
  boundary are KEPT, never dropped — same side as the reference's
  ``probs >= cutoff``).
* **the draw** — greedy rows take the argmax over the kept set minus
  the forbid token (exactly the reference's argmax over its top-p-
  filtered, forbid-masked log-weights — token-identical by
  construction: identical mask arithmetic, identical temperature
  division, identical first-index tie-break).  Sampled rows draw by
  inverse CDF: a per-row uniform (split from the same jax PRNG key
  stream as the reference) binary-searches the kept-mass CDF —
  distribution-preserving, not bitwise-identical to
  ``jax.random.categorical``'s Gumbel race (the seeded statistical
  tests are the contract, exactly like the speculative loop's
  rejection-sampling residual).
* **forbid** — the speculative loop's rejection-sampling residual token
  is masked AFTER the top-p filter (reference semantics): excluded from
  the argmax and the draw, but not from the nucleus statistics.

Kept OUTSIDE the kernel (cheap ``[B]``-shaped ops): the ``accepting``
EOS-gate gather, the uniform draw, the dead-end EOS override, and the
DFA transition gather ``tables[dfa, state, tok]`` — fusing those would
add table DMA for no measurable win; the ``[B, V]`` work is the point.

Selection: ``EngineConfig.fused_sampler`` / ``BCG_TPU_FUSED_SAMPLER``
(auto = pallas on TPU, xla elsewhere; explicit pallas off-TPU runs the
kernel in interpret mode — the parity-test path).  The XLA sampler
(``make_masked_sampler``) stays the conformance oracle, shared verbatim
by all three decode-loop families exactly as before.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30
_LANES = 128
# Bisection iteration counts: the top-p threshold converges to
# ~range * 2^-iters (fp32-exact at 30), the CDF walk needs
# ceil(log2(V)) <= 21 for any real vocabulary.
_TOPP_ITERS = 30
_CDF_ITERS = 21
# Log-prob range the threshold scan covers: tokens more than e^-30
# (~1e-13) below the max carry no samplable mass at any top_p < 1.
_TOPP_RANGE = 30.0

# Engine-resolved impl markers (mirror ops/paged_attention.PALLAS*).
XLA = "xla"
PALLAS = "sampler_pallas"
PALLAS_INTERPRET = "sampler_pallas_it"

# Geometry guard: padded vocab rows above this would not fit the
# kernel's whole-row-in-VMEM design (a few f32 [V] temporaries).  Every
# real tokenizer is far below it; module-level so tests can shrink it
# to exercise the engine's fallback warning.
MAX_VOCAB = 1 << 20


def _sampler_kernel(
    dfa_ref, st_ref, logits_ref, minb_ref, meta_i_ref, meta_f_ref, out_ref,
    *, eos_id, top_p, vocab,
):
    """One row's full pipeline.  ``logits_ref`` ``[1, Vs, 128]`` f32;
    ``minb_ref`` ``[1, 1, Vs, 128]`` (the row's DFA-state slice, placed
    by the scalar-prefetch index map); ``meta_i`` ``[1, 1, 4]`` /
    ``meta_f`` ``[1, 1, 2]`` SMEM rows (exactly the scalars the program
    needs — every extra stacked lane is a host-side op the while-body
    census charges against the fusion win); ``out_ref`` ``[1, 1, 128]``
    int32 ``[token, any_tok, 0...]``.  All reductions run in f32 —
    Mosaic has no integer reductions — and token indices stay exact in
    f32 (every vocab is far below 2^24)."""
    budget_left = meta_i_ref[0, 0, 0]
    forbid = meta_i_ref[0, 0, 1]
    greedy = meta_i_ref[0, 0, 2]
    eos_ok = meta_i_ref[0, 0, 3]
    temp = meta_f_ref[0, 0, 0]
    u = meta_f_ref[0, 0, 1]
    shape = logits_ref.shape[1:]                       # (Vs, 128)
    sub = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    vid = sub * _LANES + lane
    real = vid < vocab
    # The allowed mask EXACTLY as the XLA reference computes it:
    # min_budget (budget to acceptance including this token) within the
    # row's remaining budget.  any_tok is taken BEFORE the EOS patch,
    # like the reference (a state whose only continuation is EOS counts
    # as a dead end and force-emits EOS either way).
    mb = minb_ref[0, 0].astype(jnp.int32)
    allowed = (mb <= budget_left) & real
    any_tok = jnp.max(allowed.astype(jnp.float32)) > 0.0
    scaled = logits_ref[0] / temp
    is_eos = vid == eos_id
    # Boolean algebra, not jnp.where over two i1 vectors: Mosaic lowers
    # that select through an i8 -> i1 truncation it does not support.
    gate = (is_eos & (eos_ok > 0)) | (~is_eos & allowed)
    x = jnp.where(gate, scaled, _NEG_INF)
    is_forbid = (vid == forbid) & (forbid >= 0)
    vid_f = vid.astype(jnp.float32)
    # Masked-softmax stats (forbid INCLUDED — the reference's top-p
    # filter runs before the forbid mask).
    m = jnp.max(x)
    e = jnp.where(x > _NEG_INF * 0.5, jnp.exp(x - m), 0.0)
    if top_p < 1.0:
        # Threshold scan: bisect mass(t) = sum_{x-m >= t} e over the
        # log-prob range.  Invariant: mass(lo) >= top_p * Z, mass(hi)
        # below it — lo converges (from below) onto the reference
        # cutoff's log-prob, and >= keeps boundary ties.
        z = jnp.sum(e)
        t_mass = top_p * z

        def bisect(_, lohi):
            lo, hi = lohi
            mid = 0.5 * (lo + hi)
            mass = jnp.sum(jnp.where(x - m >= mid, e, 0.0))
            keep = mass >= t_mass
            return jnp.where(keep, mid, lo), jnp.where(keep, hi, mid)

        lo, _ = jax.lax.fori_loop(
            0, _TOPP_ITERS, bisect,
            (jnp.float32(-_TOPP_RANGE), jnp.float32(1e-6)),
        )
        kept = (x - m) >= lo
    else:
        kept = x > _NEG_INF * 0.5
    # Greedy argmax over the kept set MINUS forbid — exactly the
    # reference's argmax over the top-p-filtered, forbid-masked
    # log-weights (the nucleus always contains the max, so without a
    # forbid this equals the unfiltered argmax; WITH one, the runner-up
    # must come from inside the nucleus).  First-index tie-break
    # (jnp.argmax semantics).
    sel = kept & ~is_forbid
    xg = jnp.where(sel, x, _NEG_INF)
    amax = jnp.max(xg)
    greedy_tok = jnp.min(jnp.where(sel & (xg == amax), vid_f, jnp.float32(2**24)))
    # Inverse-CDF draw over the kept mass, forbid excluded (the
    # renormalized residual): smallest token id whose inclusive kept
    # CDF exceeds u * total — a log2(V) binary search of masked sums.
    w = jnp.where(sel, e, 0.0)
    target = u * jnp.sum(w)

    def cdf_step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        below = jnp.sum(jnp.where(vid <= mid, w, 0.0))
        up = below > target
        return jnp.where(up, lo, mid), jnp.where(up, mid, hi)

    _, samp_tok = jax.lax.fori_loop(
        0, _CDF_ITERS, cdf_step,
        (jnp.int32(-1), jnp.int32(shape[0] * _LANES - 1)),
    )
    tok = jnp.where(greedy > 0, greedy_tok.astype(jnp.int32), samp_tok)
    # Lane-width output row (a (1, 1, 8) int32 block would fight
    # Mosaic's lane tiling): slot 0 = token, slot 1 = any_tok.
    lane_o = jax.lax.broadcasted_iota(jnp.int32, (1, 1, _LANES), 2)
    out_ref[...] = (
        jnp.where(lane_o == 0, tok, 0)
        + jnp.where(lane_o == 1, any_tok.astype(jnp.int32), 0)
    )


def _sampler_call(
    logits3, minb4, meta_i, meta_f, dfa_ids, states,
    eos_id: int, top_p: float, vocab: int, interpret: bool,
):
    """pallas_call wrapper: ``logits3`` ``[B, Vs, 128]`` f32; ``minb4``
    ``[n_dfa, n_states, Vs, 128]``; ``meta_i`` ``[B, 1, 4]`` int32 /
    ``meta_f`` ``[B, 1, 2]`` f32 (exact-size SMEM rows — see
    ``_sampler_kernel``); ``dfa_ids``/``states`` ``[B]`` int32
    scalar-prefetch operands.
    Returns ``[B, 1, 128]`` int32.  Deliberately NOT jitted: the caller
    is always inside a decode loop's trace, and a nested jit would
    lower as a private function call — hiding the kernel's
    ``tpu_custom_call`` from the census's while-body op attribution."""
    B, Vs, _ = logits3.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Vs, _LANES), lambda b, d, s: (b, 0, 0)),
            pl.BlockSpec((1, 1, Vs, _LANES), lambda b, d, s: (d[b], s[b], 0, 0)),
            pl.BlockSpec((1, 1, 4), lambda b, d, s: (b, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 2), lambda b, d, s: (b, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, _LANES), lambda b, d, s: (b, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(
            _sampler_kernel, eos_id=eos_id, top_p=top_p, vocab=vocab,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, _LANES), jnp.int32),
        interpret=interpret,
    )(dfa_ids, states, logits3, minb4, meta_i, meta_f)


def vocab_rows(V: int):
    """(padded vocab, sublane rows) for the ``[Vs, 128]`` row layout —
    the engine's geometry guard reads the padded width."""
    Vp = -(-V // _LANES) * _LANES
    return Vp, Vp // _LANES


def make_fused_sampler(eos_id: int, top_p: float, interpret: bool = False,
                       mesh=None):
    """Fused drop-in for ``make_masked_sampler``'s closure — identical
    signature and semantics; greedy rows token-identical, sampled rows
    distribution-preserving (see module docstring).

    ``mesh``: the kernel needs a row's WHOLE vocab in VMEM, so under a
    mesh (where the lm_head leaves logits vocab-sharded over ``tp``, and
    a bare Mosaic call cannot be partitioned) the call is shard_map'd
    with the vocab replicated — one all-gather of ``[B, V]`` f32 per
    step — and rows split over ``dp`` when they divide."""

    def call(B, V):
        kernel = functools.partial(
            _sampler_call, eos_id=eos_id, top_p=float(top_p), vocab=V,
            interpret=interpret,
        )
        if mesh is None:
            return kernel
        dp = mesh.shape.get("dp", 1)
        rows = P("dp" if dp > 1 and B % dp == 0 else None)
        return jax.shard_map(
            kernel, mesh=mesh,
            in_specs=(rows, P(), rows, rows, rows, rows), out_specs=rows,
            # pallas_call results carry no varying-axes type.
            check_vma=False,
        )

    def masked_sample(logits, states, rng, emitted,
                      tables, accepting, min_budget, dfa_ids,
                      row_temp, row_budget, forbid=None):
        B, V = logits.shape
        Vp, Vs = vocab_rows(V)
        clamped = jnp.maximum(states, 0).astype(jnp.int32)
        budget_left = (row_budget - emitted).astype(jnp.int32)
        eos_ok = accepting[dfa_ids, clamped]
        greedy_row = row_temp <= 0.0
        safe_temp = jnp.where(greedy_row, 1.0, row_temp).astype(jnp.float32)
        rng, sub = jax.random.split(rng)
        u = jax.random.uniform(sub, (B,), jnp.float32)
        fb = (
            forbid.astype(jnp.int32) if forbid is not None
            else jnp.full((B,), -1, jnp.int32)
        )
        lg = logits.astype(jnp.float32)
        mb = min_budget
        if Vp != V:
            # Off-lane vocab (no real preset needs it): pad tokens are
            # forbidden via the sentinel, so the kernel's `real` guard
            # is belt and suspenders.  Loop-invariant — XLA hoists it.
            lg = jnp.pad(lg, ((0, 0), (0, Vp - V)))
            mb = jnp.pad(
                mb, ((0, 0), (0, 0), (0, Vp - V)),
                constant_values=jnp.iinfo(mb.dtype).max,
            )
        logits3 = lg.reshape(B, Vs, _LANES)
        minb4 = mb.reshape(mb.shape[0], mb.shape[1], Vs, _LANES)
        meta_i = jnp.stack(
            [budget_left, fb, greedy_row.astype(jnp.int32),
             eos_ok.astype(jnp.int32)],
            axis=1,
        )[:, None, :]
        meta_f = jnp.stack([safe_temp, u], axis=1)[:, None, :]
        out = call(B, V)(
            logits3, minb4, meta_i, meta_f,
            dfa_ids.astype(jnp.int32), clamped,
        )
        tok = out[:, 0, 0]
        any_tok = out[:, 0, 1] > 0
        # Dead end (no token allowed): force EOS — identical to the
        # XLA reference's post-draw override.
        tok = jnp.where(any_tok, tok, eos_id).astype(jnp.int32)
        next_states = tables[dfa_ids, clamped, tok].astype(jnp.int32)
        next_states = jnp.where(tok == eos_id, -1, next_states)
        return tok, next_states, rng

    return masked_sample
