"""The arithmetic of the comparison, on logits made by hand: the
reference is stood in for by a table, the rows by plain objects."""

import types

import numpy as np
import pytest

from lib import correct

SCHEMA = {"type": "object",
          "properties": {"internal_strategy": {"type": "string", "minLength": 3}},
          "required": ["internal_strategy"], "additionalProperties": False}
HEAD = '{"internal_strategy":"'
CONFIG = {"control": {"weights": "int4"}}
TRAFFIC = {"compare": {"kinds": ["decide"], "positions": 512, "skip_last_tokens": 8}}


def _call(texts, temps, budget=200):
    n = len(texts)
    return types.SimpleNamespace(
        kind="decide", rows=n, texts=texts, temps=temps, budgets=[budget] * n,
        schemas=[SCHEMA] * n, prompt_ids=[[65 + i] * (5 + i) for i in range(n)])


def _body(rng, probs, letters, n):
    return "".join(rng.choice(letters, size=n, p=probs))


@pytest.fixture
def table(monkeypatch):
    """Position-independent logits over the bytes: a reference whose
    distribution inside a string is known.  At the control's weights
    every logit is 0.6 off, the same way at every position."""
    rng = np.random.default_rng(3)
    base = rng.normal(0.0, 1.0, correct.COLS)

    def logits(config, seed, tokens, lengths, cols, weights="bf16"):
        shift = 0.0 if weights == "bf16" else 0.6
        noise = np.random.default_rng(9).normal(0.0, shift, cols)
        return np.broadcast_to(base + noise, tokens.shape + (cols,)).astype(np.float32)

    monkeypatch.setattr(correct, "reference_for",
                        lambda config: types.SimpleNamespace(logits=logits))
    return base


def _string_probs(base, temp):
    from lib.grammar import Grammar

    ids = sorted(Grammar(SCHEMA).allowed(HEAD + "abcd"))
    logp = correct._log_softmax(base[ids] / temp)
    keep = [i for i in ids if i not in (34, 92)]          # stay inside the string
    p = np.exp(logp[[ids.index(i) for i in keep]])
    return [chr(i) for i in keep], p / p.sum(), ids


def _sample(texts, temps):
    return correct.distinct_rows([_call(texts, temps)], ["decide"])


def test_sampled_numbers_are_nought_for_the_references_own_draws(table):
    temp = 0.5
    letters, p, _ = _string_probs(table, temp)
    rng = np.random.default_rng(0)
    texts = [HEAD + _body(rng, p, letters, 170) for _ in range(12)]
    sample = _sample(texts, [temp] * 12)
    own = correct.positions(CONFIG, TRAFFIC, 1, sample)
    out = correct.numbers(own)
    assert out["sampled_tokens"] == 12 * 192 and out["off_grammar_tokens"] == 0
    # draws conditioned on not closing the string: a few hundredths at most
    assert abs(out["sampled_excess_nats"]) < 0.08
    assert abs(out["served_histogram_chi2"]) < 0.03
    # the control in the program's place: its own draws at the same positions
    low = correct.positions(CONFIG, TRAFFIC, 1, sample, "int4")
    as_control = correct.numbers(correct.as_control(own, low, 1))
    assert as_control["served_histogram_chi2"] > 0.2


def test_histogram_chi2_by_hand():
    # 100 positions, two bytes expected evenly (50 and 50, chance 25 each),
    # byte 0 served every time: 2 x (50^2 - 25) / 50 over 100 tokens.  With
    # 2 positions the bytes fall under _RARE and share one bin: nought.
    p = np.full((100, 2), 0.5)
    assert correct.histogram_chi2(p, np.zeros(100, np.int64)) == pytest.approx(0.99)
    assert correct.histogram_chi2(p[:2], np.zeros(2, np.int64)) == pytest.approx(0.0)


def test_greedy_gap_and_altered_token(table):
    letters, p, ids = _string_probs(table, 1.0)
    best = letters[int(np.argmax(p))]
    own = correct.positions(CONFIG, TRAFFIC, 1, _sample([HEAD + best * 170], [0.0]))
    out = correct.numbers(own)
    assert out["greedy_tokens"] == 192 and out["greedy_gap_max"] <= 1e-6
    assert out["served_histogram_chi2"] != out["served_histogram_chi2"]   # no sampled row
    low = correct.positions(CONFIG, TRAFFIC, 1, _sample([HEAD + best * 170], [0.0]), "int4")
    assert correct.numbers(correct.as_control(own, low, 1))["greedy_gap_max"] >= 0.0
    worst = letters[int(np.argmin(p))]
    texts = [HEAD + best * 50 + worst + best * 119]
    out = correct.compare_rows(CONFIG, TRAFFIC, 1, _sample(texts, [0.0]))
    assert out["greedy_gap_max"] > 2.0
    texts = [HEAD + best * 50 + "\x07" + best * 119]      # no byte the grammar allows
    assert correct.compare_rows(CONFIG, TRAFFIC, 1, _sample(texts, [0.0]))["off_grammar_tokens"] >= 1


def test_more_rows_than_a_block_and_a_row_served_twice(table):
    texts = [HEAD + "a" * (10 + n) for n in range(13)]
    call = _call(texts, [0.0] * 13)
    rows = correct.distinct_rows([call, call], ["decide"])
    assert len(rows) == 13                     # the replayed call counts once
    own = correct.positions(CONFIG, TRAFFIC, 1, rows)
    assert sorted(set(own["row"])) == list(range(13))
    assert correct.distinct_rows([call], ["vote"]) == []


def test_verdict_holds_every_limit():
    ok, table_ = correct.verdict({"a": 0.1, "b": 0}, {"a": 0.2, "b": 0})
    assert ok and table_["a"] == {"value": 0.1, "limit": 0.2}
    assert not correct.verdict({"a": 0.3, "b": 0}, {"a": 0.2, "b": 0})[0]
    assert not correct.verdict({"a": float("nan"), "b": 0}, {"a": 0.2, "b": 0})[0]
    assert not correct.verdict({"b": 0}, {"a": 0.2, "b": 0})[0]
