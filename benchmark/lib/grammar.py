"""Which next byte keeps a JSON answer inside its schema: the plain side
of the guided sampler's mask.

The deployment's contract for a guided row, as the configuration states
it: the answer is the COMPACT serialisation (no whitespace between
tokens) of an object whose properties come in declaration order, strings
are printable ASCII with JSON's two-character escapes, integers carry no
leading zeros.  This module turns a schema into an ordinary regular
expression over bytes and asks the ``regex`` library whether a prefix can
still grow into a full match.  It imports nothing of the program.
"""

from __future__ import annotations

import json

import regex

_CONTENT = r'[ !#-\[\]-~]'           # printable ASCII but " and \
_ESCAPE = r'\\["\\/ntrbf]'
_CHAR = f"(?:{_CONTENT}|{_ESCAPE})"
_MAX_ENUMERATED = 4096


def _lit(value) -> str:
    return regex.escape(json.dumps(value, ensure_ascii=True), special_only=True)


def _integer(schema: dict) -> str:
    lo, hi = schema.get("minimum"), schema.get("maximum")
    if lo is None or hi is None:
        if lo is None and hi is None:
            return r"-?(?:0|[1-9][0-9]*)"
        raise ValueError(f"half-open integer range not supported: {schema!r}")
    if hi - lo > _MAX_ENUMERATED:
        raise ValueError(f"integer range too wide to enumerate: {schema!r}")
    return "(?:" + "|".join(str(v) for v in range(int(lo), int(hi) + 1)) + ")"


def schema_regex(schema: dict) -> str:
    """Regular expression of the compact serialisations of ``schema``."""
    if "enum" in schema:
        return "(?:" + "|".join(_lit(v) for v in schema["enum"]) + ")"
    if "const" in schema:
        return _lit(schema["const"])
    for key in ("anyOf", "oneOf"):
        if key in schema:
            return "(?:" + "|".join(schema_regex(s) for s in schema[key]) + ")"
    kind = schema.get("type")
    if kind == "string":
        lo = int(schema.get("minLength", 0))
        hi = schema.get("maxLength")
        reps = f"{{{lo},{int(hi)}}}" if hi is not None else f"{{{lo},}}"
        return f'"{_CHAR}{reps}"'
    if kind == "integer":
        return _integer(schema)
    if kind == "boolean":
        return "(?:true|false)"
    if kind == "null":
        return "null"
    if kind == "object":
        required = set(schema.get("required", []))
        body, first = "", True
        for name, sub in schema.get("properties", {}).items():
            member = f"{_lit(name)}:{schema_regex(sub)}"
            if first:
                if name not in required:
                    raise ValueError("first property must be required")
                body, first = member, False
            elif name in required:
                body += f",{member}"
            else:
                body += f"(?:,{member})?"
        return r"\{" + body + r"\}"
    raise ValueError(f"schema outside the deployment's surface: {schema!r}")


def _max_min_length(schema) -> int:
    """Largest ``minLength`` anywhere in a schema; -1 where a string has
    a ``maxLength`` (its count then matters to the end)."""
    if isinstance(schema, dict):
        if "maxLength" in schema:
            return -1
        subs = [_max_min_length(v) for v in schema.values()]
        if -1 in subs:
            return -1
        return max([int(schema.get("minLength", 0))] + subs)
    if isinstance(schema, list):
        subs = [_max_min_length(v) for v in schema]
        return -1 if -1 in subs else max(subs + [0])
    return 0


def _longest_literal(schema) -> int:
    """Longest property name or string constant of a schema, as served."""
    if isinstance(schema, dict):
        own = [len(json.dumps(k)) for k in schema.get("properties", {})] \
            if isinstance(schema.get("properties"), dict) else []
        own += [len(json.dumps(v)) for v in schema.get("enum", [])]
        if "const" in schema:
            own.append(len(json.dumps(schema["const"])))
        return max(own + [_longest_literal(v) for v in schema.values()] + [0])
    if isinstance(schema, list):
        return max([_longest_literal(v) for v in schema] + [0])
    return 0


def _shorten(text: str, keep: int) -> str:
    """``text`` with every string body cut to its first ``keep`` string
    characters (an escape pair is one): the same state of the grammar as
    long as no string counts beyond ``keep``.  A backslash left open at
    the very end is kept."""
    out, inside, n, i = [], False, 0, 0
    while i < len(text):
        c = text[i]
        if not inside:
            out.append(c)
            if c == '"':
                inside, n = True, 0
            i += 1
        elif c == '"':
            out.append(c)
            inside = False
            i += 1
        else:
            unit = text[i:i + 2] if c == "\\" else c
            if n < keep or (c == "\\" and len(unit) == 1):
                out.append(unit)
            n += 1
            i += len(unit)
    return "".join(out)


EOS = 256      # the byte vocabulary's end-of-sequence id


class Grammar:
    """Prefix oracle of one schema."""

    def __init__(self, schema: dict):
        self._pattern = regex.compile(schema_regex(schema), regex.DOTALL)
        longest = _max_min_length(schema)
        # no literal is ever cut, no free string below where it stops counting
        self._keep = (max(longest + 2, _longest_literal(schema) + 1)
                      if longest >= 0 else None)
        self._memo: dict = {}

    def viable(self, text: str) -> bool:
        """Can ``text`` still grow into (or is it already) a full answer?"""
        return self._pattern.fullmatch(text, partial=True) is not None

    def complete(self, text: str) -> bool:
        m = self._pattern.fullmatch(text, partial=True)
        return m is not None and not m.partial

    def allowed(self, prefix: str) -> frozenset:
        """Ids that may come after ``prefix``: byte values, and ``EOS``
        once the answer is complete.  Memoised on the prefix with its
        string bodies cut short, which leaves the grammar in the same
        state (strings only count up to their ``minLength``)."""
        key = _shorten(prefix, self._keep) if self._keep is not None else prefix
        got = self._memo.get(key)
        if got is None:
            ids = {b for b in range(256) if self.viable(key + chr(b))}
            if self.complete(key):
                ids.add(EOS)
            got = self._memo[key] = frozenset(ids)
        return got
