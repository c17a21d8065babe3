"""The plain grammar against the program's own token DFA: the two were
written apart and have to allow the same next bytes."""

import random

import pytest

from lib.grammar import Grammar

SCHEMAS = {
    "honest_decide": {
        "type": "object",
        "properties": {
            "internal_strategy": {"type": "string", "minLength": 3},
            "value": {"type": "integer", "minimum": 0, "maximum": 50},
            "public_reasoning": {"type": "string", "minLength": 10},
        },
        "required": ["internal_strategy", "value", "public_reasoning"],
        "additionalProperties": False,
    },
    "byzantine_decide": {
        "type": "object",
        "properties": {
            "internal_strategy": {"type": "string", "minLength": 3},
            "value": {"anyOf": [{"type": "integer", "minimum": 0, "maximum": 50},
                                {"type": "string", "enum": ["abstain"]}]},
            "public_reasoning": {"type": "string"},
        },
        "required": ["internal_strategy", "value"],
        "additionalProperties": False,
    },
    "vote": {
        "type": "object",
        "properties": {"decision": {"type": "string",
                                    "enum": ["stop", "continue", "abstain"]}},
        "required": ["decision"],
        "additionalProperties": False,
    },
}


def test_prefixes_by_hand():
    g = Grammar(SCHEMAS["honest_decide"])
    assert g.viable('{"internal_strategy":"ab')
    assert not g.viable('{"internal_strategy":"ab"')          # minLength 3
    assert not g.viable('{ "internal_strategy"')               # compact only
    assert g.viable('{"internal_strategy":"abc","value":5')
    assert not g.viable('{"internal_strategy":"abc","value":51')
    assert not g.viable('{"internal_strategy":"abc","value":05')
    full = '{"internal_strategy":"abc","value":7,"public_reasoning":"0123456789"}'
    assert g.complete(full) and not g.complete(full[:-1])
    assert not g.viable(full + "}")


@pytest.mark.parametrize("name", sorted(SCHEMAS))
def test_same_next_bytes_as_the_programs_dfa(name):
    """Random walks through the program's byte-level DFA of the compact
    grammar: at every state the plain grammar allows the same bytes."""
    from bcg_tpu.guided.dfa import ast_to_dfa
    from bcg_tpu.guided.regex_ast import EPS
    from bcg_tpu.guided.schema_compiler import schema_to_ast

    automaton = ast_to_dfa(schema_to_ast(SCHEMAS[name], ws=EPS))
    g = Grammar(SCHEMAS[name])
    rng = random.Random(0)
    for _walk in range(6):
        state, text = automaton.start, ""
        for _step in range(90):
            allowed = {b for b in range(256) if automaton.transitions[state, b] >= 0}
            mine = {b for b in range(256) if g.viable(text + chr(b))}
            assert mine == allowed, (text, sorted(mine ^ allowed))
            assert g.complete(text) == bool(automaton.accepting[state])
            if not allowed:
                break
            # mostly leave a string as soon as the grammar lets the walk
            quote = ord('"')
            b = quote if quote in allowed and rng.random() < 0.3 \
                else rng.choice(sorted(allowed))
            state, text = int(automaton.transitions[state, b]), text + chr(b)
