"""The comparison that decides ``correct``.

What is compared is what the timed path itself served in the window, at
the timed sizes: every distinct row of the call kinds the traffic file
names, each served token against the plain float32 reference run once
over the row's prompt and served tokens, in blocks of rows, after the
program's state is freed.  All numbers measure how far what was served
lies from the reference; none needs a second, lower-precision pass (the
control is read by ``tools/limits.py`` and ``tests/test_control.py``,
never by a run).

* Greedy rows: ``greedy_gap_max``, the widest gap by which a served
  token's reference logit lies below the reference's best token that the
  answer's grammar allows there.
* Sampled rows (temperature T), p the reference's distribution at a
  position (softmax of the allowed logits over T):
  ``served_histogram_chi2``, the chi-square distance between how often
  each byte was served and how often the reference expects it over the
  same positions (``sum_t (O_t - E_t)^2 / E_t``, less what draws from p
  would give by chance, over the number of tokens; bytes expected fewer
  than ``_RARE`` times are pooled).  A program's rounding error is the
  same at every position (the same weights), so it moves the histogram
  at first order, while chance moves it by one over the root of the
  count: the number reads nought for draws from p and grows with the
  square of the logit error whatever its direction.
  ``sampled_excess_nats``, the tokens' mean surprise under p less p's
  entropy, is printed beside it and not held (sampling noise of some
  0.05 nats at 1,900 tokens hides W4 from W8; PERF.md section 6).

Beside them, exact checks: no served token outside the grammar, every
row of the window valid against its schema, no failed row.
"""

from __future__ import annotations

import importlib

import numpy as np

from .grammar import EOS, Grammar

COLS = 257         # bytes and EOS; ids above carry no bytes and are never legal
_RARE = 5.0        # bytes expected fewer times than this share one bin
_BLOCK = 10        # rows a reference pass holds at once


def reference_for(config: dict):
    """The plain reference the configuration's file names
    (``"reference": "<name>"`` is ``benchmark/references/<name>.py``):
    one function ``logits(cfg, seed, tokens, lengths, cols, weights)``
    that imports nothing of the program, makes its weights from ``seed``
    at the stated precision (or at the file's ``control``), runs in
    float32 at ``highest`` layer by layer, and returns float32
    ``[rows, positions, cols]``."""
    return importlib.import_module("references." + config["reference"])


def distinct_rows(calls, kinds) -> list:
    """(call, row) of every guided row of the named call kinds that the
    window served; a row served again (a proved round played twice)
    counts once."""
    seen, out = set(), []
    for c in calls:
        if c.kind not in kinds:
            continue
        for i in range(c.rows):
            key = (c.kind, c.texts[i], tuple(c.prompt_ids[i]))
            if c.schemas[i] is not None and key not in seen:
                seen.add(key)
                out.append((c, i))
    return out


def served_ids(text: str, budget: int) -> list:
    ids = list(text.encode("latin-1"))
    return ids + [EOS] if len(ids) < budget else ids


def _log_softmax(x: np.ndarray) -> np.ndarray:
    x = x - x.max(axis=-1, keepdims=True)
    return x - np.log(np.exp(x).sum(axis=-1, keepdims=True))


def positions(config: dict, traffic: dict, seed: int, sample: list,
              weights: str = "bf16") -> dict:
    """The reference over the sampled rows; one entry per compared
    position: ``scores`` [P, COLS] the reference's logits there (minus
    infinity where the grammar allows no such byte), ``token`` the byte
    served, ``temp`` its row's temperature, ``row`` its row, and
    ``off_grammar`` the count of served tokens the grammar does not
    allow.  ``weights`` other than the stated ones gives the same table
    at a lower precision (the control's)."""
    cmp_cfg = traffic["compare"]
    T, skip_last = cmp_cfg["positions"], cmp_cfg["skip_last_tokens"]
    reference = reference_for(config)
    scores, token, temp, row = [], [], [], []
    off_grammar = 0
    for first in range(0, len(sample), _BLOCK):
        block = sample[first:first + _BLOCK]
        tokens = np.zeros((_BLOCK, T), np.int32)
        lengths = np.ones((_BLOCK,), np.int32)
        for r, (call, i) in enumerate(block):
            seq = list(call.prompt_ids[i]) + served_ids(call.texts[i], call.budgets[i])[:-1]
            if len(seq) > T:
                raise RuntimeError(f"row of {len(seq)} tokens exceeds compare.positions={T}")
            tokens[r, : len(seq)] = seq
            lengths[r] = len(seq)
        ref = reference.logits(config, seed, tokens, lengths, COLS, weights)
        for r, (call, i) in enumerate(block):
            n, text, budget = len(call.prompt_ids[i]), call.texts[i], call.budgets[i]
            grammar = Grammar(call.schemas[i])
            for j, t in enumerate(served_ids(text, budget)):
                if j >= budget - skip_last:
                    break
                allowed = sorted(grammar.allowed(text[:j]))
                if t not in allowed:
                    off_grammar += 1
                    continue
                line = np.full((COLS,), -np.inf, np.float64)
                line[allowed] = ref[r, n - 1 + j, allowed]
                scores.append(line)
                token.append(t)
                temp.append(float(call.temps[i]))
                row.append(first + r)
    return {"scores": np.array(scores).reshape(-1, COLS), "token": np.array(token, np.int64),
            "temp": np.array(temp, np.float64), "row": np.array(row, np.int64),
            "off_grammar": off_grammar}


def histogram_chi2(p: np.ndarray, token: np.ndarray) -> float:
    """``p`` [N, COLS] the expected distribution at each of N positions,
    ``token`` [N] what was served there."""
    n = len(token)
    served = np.bincount(token, minlength=p.shape[1]).astype(np.float64)
    expected = p.sum(axis=0)
    rare = expected < _RARE
    p_rare = p[:, rare].sum(axis=1)
    served = np.append(served[~rare], served[rare].sum())
    chance = np.append((p[:, ~rare] * (1.0 - p[:, ~rare])).sum(axis=0),
                       (p_rare * (1.0 - p_rare)).sum())
    expected = np.append(expected[~rare], expected[rare].sum())
    keep = expected > 0.0
    distance = ((served - expected) ** 2 - chance)[keep] / expected[keep]
    return float(distance.sum() / n)


def numbers(table: dict) -> dict:
    """The numbers compared, from a table of positions."""
    nan = float("nan")
    scores, token, temp = table["scores"], table["token"], table["temp"]
    at = np.arange(len(token))
    greedy = temp == 0.0
    gaps = scores[greedy].max(axis=1) - scores[greedy][at[: greedy.sum()], token[greedy]] \
        if greedy.any() else np.zeros((0,))
    out = {"greedy_tokens": int(greedy.sum()),
           "greedy_gap_max": float(gaps.max()) if len(gaps) else nan,
           "sampled_tokens": int((~greedy).sum()),
           "served_histogram_chi2": nan, "sampled_excess_nats": nan,
           "off_grammar_tokens": int(table["off_grammar"])}
    if (~greedy).any():
        tok = token[~greedy]
        logp = _log_softmax(scores[~greedy] / temp[~greedy, None])
        p = np.exp(logp)
        plogp = np.where(p > 0.0, p * np.where(p > 0.0, logp, 0.0), 0.0)
        surprise = -logp[at[: len(tok)], tok]
        out["sampled_excess_nats"] = float(np.mean(surprise + plogp.sum(axis=1)))
        out["served_histogram_chi2"] = histogram_chi2(p, tok)
    return out


def as_control(table: dict, low: dict, seed: int) -> dict:
    """The control put in the program's place, without decoding: at each
    position of the same prompts and served tokens, the token the lower
    precision (``low``, the same positions at the control's weights)
    puts first where the row is greedy, and one drawn from its
    distribution (from the seed) where the row is sampled; judged, like
    the program, by the reference's ``table``."""
    rng = np.random.default_rng(seed)
    token = table["token"].copy()
    for k, (line, temp) in enumerate(zip(low["scores"], table["temp"])):
        if temp == 0.0:
            token[k] = int(np.argmax(line))
        else:
            q = np.exp(_log_softmax(line / temp))
            token[k] = int(rng.choice(len(q), p=q / q.sum()))
    return dict(table, token=token)


def compare_rows(config: dict, traffic: dict, seed: int, sample: list) -> dict:
    return numbers(positions(config, traffic, seed, sample))


def invalid_rows(calls) -> int:
    """Rows of the window that do not parse, do not validate against
    their schema (``jsonschema``), or are not the compact form the
    grammar describes."""
    import json

    import jsonschema

    bad = 0
    grammars = {}
    for call in calls:
        for text, schema in zip(call.texts, call.schemas):
            if schema is None:
                continue
            key = json.dumps(schema, sort_keys=True)
            g = grammars.setdefault(key, Grammar(schema))
            try:
                jsonschema.validate(json.loads(text), schema)
                ok = g.complete(text)
            except (ValueError, jsonschema.ValidationError):
                ok = False
            bad += not ok
    return bad


def verdict(numbers: dict, limits: dict) -> tuple:
    """Each number compared beside its limit; all must hold."""
    table = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        if value != value:          # nothing was compared: not a number, not held
            value = None
        held = value is not None and value <= limit
        ok = ok and held
        table[name] = {"value": value, "limit": limit}
    return ok, table
