"""Prompt-lookup speculative decoding: drafter + acceptance kernel.

BCG decode output is short, highly repetitive JSON — agents echo
integers, keys, and vote strings that already appear verbatim in their
prompt history — so draft-model-free speculation pays unusually well
here.  Each decode-loop iteration:

1. samples ONE token through the guided masked sampler (exactly the
   plain loop's sampler — shared from this module so the equivalence
   guarantee is by construction, not by parallel maintenance),
2. drafts up to K continuation tokens by matching the last N tokens of
   the row's history (prompt + output so far) against that history and
   proposing the continuation of the most recent match, falling back to
   the DFA's forced chain wherever the n-gram source runs dry — forced
   chains are the degenerate always-accepted draft,
3. walks the draft through the token DFA *during* drafting, truncating
   at the first grammar- or budget-illegal token (an accepted token is
   therefore legal by construction, the guaranteed-parse invariant the
   plain loop gets from its per-step mask),
4. verifies the whole [sampled + draft] chunk in ONE forward pass
   (``models/transformer.decode_chunk_spec`` — K+1 positions, logits
   returned at every position, KV written at per-row compacted slots),
5. accepts the longest draft prefix the model agrees with: greedy rows
   accept while the draft token equals the masked argmax (token-identical
   to the plain loop by construction); sampled rows use standard
   rejection sampling against the masked/temperature/top-p-filtered
   distribution, which is distribution-preserving — on rejection the
   NEXT iteration samples from the residual (the rejected token is
   carried as a per-row ``forbid`` and masked out after the top-p
   filter, exactly the renormalized leave-one-out distribution a
   deterministic draft's residual reduces to).

Everything lives in the ``lax.while_loop`` carry (acceptance counts,
per-row write positions, the history buffer) so varying per-row
acceptance NEVER changes a compiled shape — steady-state speculative
decode is pinned at zero retraces like the plain loop.

The numpy reference implementations at the bottom (``ngram_draft_np``,
``spec_mirror_np``) are the conformance oracle for the traced drafter
and the FakeEngine's hermetic mirror of the drafted/accepted counters.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from bcg_tpu.obs import scopes

# Defaults for the registered env flags / EngineConfig fields.  K = 4
# mirrors the forced-chain FF_CHUNK rationale (chunk MXU overhead vs
# saved weight passes); N = 3 trigrams are specific enough that most
# matches verify while still firing on short JSON echoes.
DEFAULT_SPEC_K = 4
DEFAULT_SPEC_NGRAM = 3


def spec_decode_slots(max_new: int, k: int) -> int:
    """Decode-tail cache allocation for the speculative loop.

    Per-row write positions keep every row's cache fully compacted (slot
    count == accepted tokens), so unlike the fast-forward loop's 1.5x
    compacted-window bound there is no over-allocation to amortize: one
    slot per emittable token plus one K+1-wide verify window (and the
    forced-EOS slot of a budget-exhausted row) always fits.
    """
    return max_new + k + 2


# --------------------------------------------------------------- sampler
# The guided masked sampler, shared VERBATIM by the standard,
# fast-forward, and speculative decode loops (the greedy-equivalence
# guarantee between them depends on a single implementation) — moved
# here from the engine so the speculative verify can reuse the filtered
# distribution without a circular import.


def make_masked_logits(eos_id: int, top_p: float):
    """Build the filter stage of the guided sampler: raw logits -> the
    masked / temperature-scaled / top-p-filtered log-weights the sampler
    draws from (and the acceptance test scores drafts against).

    Guaranteed parse: a token is only allowed if the state it leads to
    can still reach acceptance within the remaining budget (min_budget
    precomputed per (state, token) in GuidedBatch), so the sampler can
    never truncate into invalid JSON — e.g. with 7 tokens left it cannot
    open a minLength-10 string, and at the exact boundary only
    shortest-completion tokens survive the mask.  vLLM has no
    equivalent: its guided output just cuts off at max_tokens and fails
    to parse, which is what the reference's 3-attempt retry ladder
    (bcg_agents.py:708-759) exists to absorb.  min_budget also encodes
    "forbidden" (sentinel), so this one gather is the entire mask.
    """
    use_top_p = top_p < 1.0

    def masked_logits(logits, states, emitted,
                      tables, accepting, min_budget, dfa_ids,
                      row_temp, row_budget):
        clamped = jnp.maximum(states, 0)
        budget_left = row_budget - emitted           # [B], incl. this token
        allowed = min_budget[dfa_ids, clamped] <= budget_left[:, None]
        eos_ok = accepting[dfa_ids, clamped]
        any_tok = allowed.any(axis=-1)
        greedy_row = row_temp <= 0.0                 # [B]
        safe_temp = jnp.where(greedy_row, 1.0, row_temp)[:, None]
        scaled = logits / safe_temp
        lg = jnp.where(allowed, scaled, -jnp.inf)
        # EOS is legal exactly at accepting states (same temperature
        # scaling as every other token).
        lg = lg.at[:, eos_id].set(
            jnp.where(eos_ok, scaled[:, eos_id], -jnp.inf)
        )
        if use_top_p:
            # Nucleus filter: keep the smallest prefix of the sorted
            # distribution whose mass reaches top_p.
            probs = jax.nn.softmax(lg, axis=-1)
            sorted_probs = jnp.sort(probs, axis=-1)[:, ::-1]
            cum = jnp.cumsum(sorted_probs, axis=-1)
            cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
            cutoff = jnp.take_along_axis(sorted_probs, cutoff_idx, axis=-1)
            lg = jnp.where(probs >= cutoff, lg, -jnp.inf)
        return lg, any_tok, greedy_row

    return masked_logits


def make_masked_sampler(eos_id: int, top_p: float):
    """The full guided sampler (filter + draw) shared by every decode
    loop.  ``forbid`` (optional [B] token ids, -1 = none) masks one
    token AFTER the top-p filter — the speculative loop's
    rejection-sampling residual; the plain/fast-forward loops never pass
    it, so their traced graphs are unchanged."""
    masked_logits = make_masked_logits(eos_id, top_p)

    def masked_sample(logits, states, rng, emitted,
                      tables, accepting, min_budget, dfa_ids,
                      row_temp, row_budget, forbid=None):
        lg, any_tok, greedy_row = masked_logits(
            logits, states, emitted, tables, accepting, min_budget,
            dfa_ids, row_temp, row_budget,
        )
        if forbid is not None:
            # Residual of a rejected deterministic draft: drop exactly
            # that token and renormalize (the categorical below).  A
            # forced (sole-legal) token is never rejected — greedy rows
            # reject only when the argmax differs, sampled rows accept
            # probability-1 mass — so this can never empty the support.
            V = lg.shape[-1]
            hit = (jnp.arange(V)[None, :] == forbid[:, None]) & (
                forbid >= 0
            )[:, None]
            lg = jnp.where(hit, -jnp.inf, lg)
        rng, sub = jax.random.split(rng)
        tok = jnp.where(
            greedy_row,
            jnp.argmax(lg, axis=-1),
            jax.random.categorical(sub, lg, axis=-1),
        )
        # Dead end (no token allowed): force EOS.
        tok = jnp.where(~any_tok, eos_id, tok)
        next_states = tables[dfa_ids, jnp.maximum(states, 0), tok].astype(
            jnp.int32
        )
        next_states = jnp.where(tok == eos_id, -1, next_states)
        return tok.astype(jnp.int32), next_states, rng

    return masked_sample


# --------------------------------------------------------------- drafter
def draft_tokens(
    hist, cur0, tok, base_states, done_or_finished,
    tables, min_budget, chain_tok, chain_len, dfa_ids,
    emitted, row_budget, *, k: int, n: int, eos_id: int,
):
    """Propose up to ``k`` draft tokens per row, DFA-truncated (traced).

    ``hist`` [B, H] int32 token history (prompt + accepted output;
    -1 pads), ``cur0`` [B] written counts, ``tok`` [B] the just-sampled
    token (not yet written into ``hist``), ``base_states`` [B] DFA
    states after ``tok``.

    The n-gram source: the most recent window of ``hist`` equal to the
    last ``n`` tokens (history tail + ``tok``); its continuation tokens
    are proposed position by position.  Wherever the source is absent,
    exhausted, diverged from the grammar, or out of budget, the state's
    forced chain (sole legal token — GuidedBatch chain tables) supplies
    the draft token instead; when neither applies the draft ends.  Every
    proposed token passes the sampler's own legality gate
    (min_budget <= remaining budget), so accepted tokens are legal by
    construction; EOS is never drafted (it ends a row through the
    sampler, exactly like the plain loop).

    Returns (draft [B, k], draft_mask [B, k], states_v [B, k],
    st_final [B]): ``states_v[:, j]`` is the DFA state after chunk
    position j (the state the acceptance test masks position j+1 with),
    ``st_final`` the state after a fully-accepted draft.
    """
    B, H = hist.shape
    W = H - n + 1
    # gram = hist[cur0-(n-1) .. cur0) + [tok]: the last n tokens once
    # tok lands.  Windows compare against the gram PREFIX from hist and
    # tok for the final element (tok is not in hist yet).
    eq = jnp.ones((B, W), bool)
    if n > 1:
        gidx = cur0[:, None] + (jnp.arange(n - 1)[None, :] - (n - 1))
        gram = jnp.take_along_axis(hist, jnp.clip(gidx, 0, H - 1), axis=1)
        for j in range(n - 1):
            eq = eq & (hist[:, j:j + W] == gram[:, j:j + 1])
    eq = eq & (hist[:, n - 1:n - 1 + W] == tok[:, None])
    s = jnp.arange(W)[None, :]
    # Window fully written, with at least one written continuation token
    # (s + n < cur0); the trivial self-match at the history tail is
    # excluded by the same bound.  The gram prefix needs n-1 written
    # tokens.
    valid_w = (s <= cur0[:, None] - n - 1) & (cur0[:, None] >= n - 1)
    score = jnp.where(eq & valid_w, s, -1)
    p = jnp.argmax(score, axis=1)                    # most recent match
    found = jnp.max(score, axis=1) >= 0
    cidx = p[:, None] + n + jnp.arange(k)[None, :]
    cont = jnp.take_along_axis(hist, jnp.clip(cidx, 0, H - 1), axis=1)
    cont_ok = found[:, None] & (cidx < cur0[:, None])

    st = base_states.astype(jnp.int32)
    ng_alive = found
    ok_prev = ~done_or_finished & (st >= 0)
    d_toks, d_ok, states_v = [], [], []
    V = min_budget.shape[-1]
    for j in range(k):
        states_v.append(st)
        stc = jnp.maximum(st, 0)
        bl = row_budget - (emitted + 1 + j)
        ng = cont[:, j]
        ng_clip = jnp.clip(ng, 0, V - 1)
        ng_legal = (
            ng_alive & cont_ok[:, j] & (ng >= 0) & (ng != eos_id)
            & (min_budget[dfa_ids, stc, ng_clip] <= bl)
        )
        ftok = chain_tok[dfa_ids, stc, 0]
        f_legal = (
            (chain_len[dfa_ids, stc] > 0) & (ftok != eos_id)
            & (min_budget[dfa_ids, stc, ftok] <= bl)
        )
        # Prefer the n-gram source (at a forced state the sole legal
        # token IS the forced token, so there is never a conflict); once
        # it diverges or runs out it stays dead for the rest of this
        # draft — its continuation no longer corresponds to the sequence
        # being built.
        d = jnp.where(ng_legal, ng_clip, ftok)
        ok = ok_prev & (ng_legal | f_legal)
        ng_alive = ng_alive & ng_legal & ok
        d = jnp.where(ok, d, 0)
        st = jnp.where(
            ok, tables[dfa_ids, stc, d].astype(jnp.int32), st
        )
        d_toks.append(d)
        d_ok.append(ok)
        ok_prev = ok
    draft = jnp.stack(d_toks, axis=1)                # [B, k]
    draft_mask = jnp.stack(d_ok, axis=1)             # [B, k]
    return draft, draft_mask, jnp.stack(states_v, axis=1), st


# ------------------------------------------------------------ acceptance
def accept_draft(
    logits_all, draft, draft_mask, states_v, emitted, rng,
    tables, accepting, min_budget, dfa_ids, row_temp, row_budget,
    *, masked_logits, eos_id: int,
):
    """Longest-accepted-prefix test over one verify pass (traced).

    ``logits_all`` [B, K1, V] from ``decode_chunk_spec`` — position j's
    logits are the model's distribution for draft index j.  Greedy rows
    accept while the draft token equals the masked argmax (exactly the
    token the plain loop would emit there); sampled rows accept draft d
    with probability p(d) under the same filtered distribution and, on
    rejection, report d as the ``forbid`` token so the next sample draws
    from the residual.  Returns (acc [B] accepted counts, forbid [B],
    next_logits [B, V] raw logits at the last accepted chunk position,
    rng).
    """
    B, K1, V = logits_all.shape
    K = K1 - 1
    ver = logits_all[:, :K].reshape(B * K, V)
    rep = lambda a: jnp.repeat(a, K, axis=0)
    emitted_v = (emitted[:, None] + 1 + jnp.arange(K)[None, :]).reshape(-1)
    lg, _any_tok, greedy_row = masked_logits(
        ver, states_v.reshape(-1), emitted_v,
        tables, accepting, min_budget, rep(dfa_ids),
        rep(row_temp), rep(row_budget),
    )
    greedy_tok = jnp.argmax(lg, axis=-1).reshape(B, K)
    p_d = jnp.take_along_axis(
        jax.nn.softmax(lg, axis=-1),
        draft.reshape(-1)[:, None], axis=1,
    )[:, 0].reshape(B, K)
    rng, sub = jax.random.split(rng)
    u = jax.random.uniform(sub, (B, K))
    match = jnp.where(
        greedy_row.reshape(B, K), draft == greedy_tok, u < p_d
    ) & draft_mask
    # Longest accepted prefix: count of leading matches.
    acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    dlen = draft_mask.sum(axis=1)
    forbid = jnp.where(
        acc < dlen,
        jnp.take_along_axis(
            draft, jnp.clip(acc, 0, K - 1)[:, None], axis=1
        )[:, 0],
        -1,
    )
    next_logits = jnp.take_along_axis(
        logits_all, acc[:, None, None], axis=1
    )[:, 0]
    return acc, forbid, next_logits, rng


# ------------------------------------------------------------- spec loop
def build_spec_loop(
    model_spec, chunk_impl: str, ring, eos_id: int, top_p: float,
    max_new: int, k: int, n: int, sampler=None,
):
    """Build the (unjitted) speculative decode loop body for
    ``JaxEngine._get_spec_decode_loop`` — same calling convention as the
    engine's other loops: one ``lax.while_loop`` on device, host-sync
    free; greedy rows are token-identical to the plain loop, sampled
    rows distribution-preserving.  Returns
    ``(out, (rng, iters), (drafted, accepted), cache)`` — the cache is
    returned ONLY so the donated input can alias the loop carry (see the
    standard loop), per-row drafted/accepted counts feed the
    ``engine.spec.*`` counters.

    ``sampler`` overrides the per-iteration guided sampler (the
    engine-resolved fused Pallas kernel, ops/guided_sampler.py —
    identical closure signature); None = the XLA reference here.  The
    VERIFY pass's filter stage (``masked_logits`` inside
    ``accept_draft``) always stays the XLA form: it scores K draft rows
    per real row, a [B*K, V] shape the per-row kernel was not built
    for, and it never draws."""
    from bcg_tpu.models.transformer import decode_chunk_spec

    masked_logits = make_masked_logits(eos_id, top_p)
    if sampler is None:
        sampler = make_masked_sampler(eos_id, top_p)
    K1 = k + 1

    @jax.named_scope(scopes.SAMPLE)
    def loop(params, cache, first_logits, valid_mask, prompt_lens, L,
             tables, accepting, min_budget, dfa_ids, init_states,
             chain_tok, chain_len, hist,
             row_temp, row_budget, rng):
        # prompt_lens doubles as the history-buffer fill count: hist row
        # i holds exactly the row's prompt tokens at [0, prompt_lens[i]).
        B = first_logits.shape[0]
        S = valid_mask.shape[1]
        Hcap = hist.shape[1]
        jr = jnp.arange(K1)[None, :]
        bidx = jnp.arange(B)[:, None]

        def cond(carry):
            i, _wp, done = carry[0], carry[1], carry[2]
            return (i < max_new) & ~done.all()

        def body(carry):
            (i, wp, done, emitted, states, forbid, logits, cache,
             valid_mask, hist, out, drafted, accepted, rng) = carry
            tok, ns, rng = sampler(
                logits, states, rng, emitted, tables, accepting,
                min_budget, dfa_ids, row_temp, row_budget, forbid=forbid,
            )
            tok = jnp.where(done, eos_id, tok)
            finished = tok == eos_id
            draft, dmask, states_v, st_final = draft_tokens(
                hist, prompt_lens + emitted, tok, ns, done | finished,
                tables, min_budget, chain_tok, chain_len, dfa_ids,
                emitted, row_budget, k=k, n=n, eos_id=eos_id,
            )
            dlen = dmask.sum(axis=1)
            chunk = jnp.concatenate([tok[:, None], draft], axis=1)
            chunk_valid = (jr == 0) | (jr - 1 < dlen[:, None])
            positions = (prompt_lens + emitted)[:, None] + jr
            logits_all, cache = decode_chunk_spec(
                params, model_spec, chunk, chunk_valid, wp, positions,
                cache, valid_mask, impl=chunk_impl, ring=ring,
            )
            acc, forbid2, next_logits, rng = accept_draft(
                logits_all, draft, dmask, states_v, emitted, rng,
                tables, accepting, min_budget, dfa_ids, row_temp,
                row_budget, masked_logits=masked_logits, eos_id=eos_id,
            )
            # Accepted chunk prefix -> out / history / attendable slots,
            # all at PER-ROW offsets (invalid and already-done positions
            # drop via OOB index).  The history write is what makes this
            # round's output draftable by the next one.
            accept_f = ((jr == 0) | (jr - 1 < acc[:, None])) & ~done[:, None]
            out_idx = jnp.where(accept_f, emitted[:, None] + jr, max_new)
            out = out.at[bidx, out_idx].set(chunk, mode="drop")
            hist_idx = jnp.where(
                accept_f, (prompt_lens + emitted)[:, None] + jr, Hcap
            )
            hist = hist.at[bidx, hist_idx].set(chunk, mode="drop")
            vm_idx = jnp.where(accept_f, wp[:, None] + jr, S)
            valid_mask = valid_mask.at[bidx, vm_idx].set(True, mode="drop")
            # State after the last accepted chunk position (= ns when
            # nothing was accepted beyond the sampled token; -1 on EOS).
            states_full = jnp.concatenate([states_v, st_final[:, None]], 1)
            next_state = jnp.take_along_axis(
                states_full, acc[:, None], axis=1
            )[:, 0]
            states = jnp.where(done, states, next_state)
            wadv = jnp.where(done, 0, 1 + acc)
            emitted = emitted + wadv
            wp = wp + wadv
            drafted = drafted + jnp.where(done, 0, dlen)
            accepted = accepted + jnp.where(done, 0, acc)
            forbid = jnp.where(done | finished, -1, forbid2)
            logits = jnp.where(done[:, None], logits, next_logits)
            done = done | finished
            return (i + 1, wp, done, emitted, states, forbid, logits,
                    cache, valid_mask, hist, out, drafted, accepted, rng)

        out = jnp.full((B, max_new), eos_id, dtype=jnp.int32)
        zi = jnp.zeros((B,), jnp.int32)
        carry = (
            jnp.int32(0), jnp.full((B,), L, jnp.int32),
            jnp.zeros((B,), bool), zi, init_states.astype(jnp.int32),
            jnp.full((B,), -1, jnp.int32), first_logits, cache,
            valid_mask, hist, out, zi, zi, rng,
        )
        (i, wp, done, emitted, states, forbid, logits, cache, valid_mask,
         hist, out, drafted, accepted, rng) = jax.lax.while_loop(
            cond, body, carry
        )
        # Returned for donation aliasing — see the standard loop.
        return out, (rng, i), (drafted, accepted), cache

    return loop


# ------------------------------------------------------ numpy references
def ngram_draft_np(
    hist: Sequence[int], tok: int, n: int, k: int
) -> List[int]:
    """Host-side oracle for the traced n-gram matcher (no DFA): the
    continuation (up to ``k`` tokens) of the most recent window of
    ``hist`` equal to the last ``n`` tokens of ``hist + [tok]``, with at
    least one written continuation token; [] when no match."""
    hist = list(hist)
    cur0 = len(hist)
    if cur0 < n - 1:
        return []
    gram = hist[cur0 - (n - 1):] + [tok]
    best = -1
    for s in range(0, cur0 - n):  # s + n < cur0
        if hist[s:s + n] == gram:
            best = max(best, s)
    if best < 0:
        return []
    return hist[best + n: best + n + k]


def spec_mirror_np(
    prompt_tokens: Sequence[int], out_tokens: Sequence[int],
    n: int, k: int, eos_id: Optional[int] = None,
) -> Tuple[int, int, int]:
    """Hermetic mirror of the speculative loop's counters for a KNOWN
    output sequence (the FakeEngine, whose "model" is its scripted
    response): runs the reference drafter over prompt + emitted-so-far
    and accepts exactly the draft prefix that agrees with the real
    continuation.  Returns (drafted, accepted, iterations) — the same
    triple the device loop reports, so hermetic serving stats and traces
    are structurally realistic."""
    hist = list(prompt_tokens)
    out = list(out_tokens)
    drafted = accepted = iters = 0
    i = 0
    while i < len(out):
        iters += 1
        tok = out[i]
        draft = [
            t for t in ngram_draft_np(hist, tok, n, k)
            if eos_id is None or t != eos_id
        ]
        good = 0
        for j, d in enumerate(draft):
            if i + 1 + j < len(out) and out[i + 1 + j] == d:
                good += 1
            else:
                break
        drafted += len(draft)
        accepted += good
        hist.extend(out[i: i + 1 + good])
        i += 1 + good
    return drafted, accepted, iters
