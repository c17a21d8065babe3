"""For every test under this directory: no test here has a traced run of
the program behind it, so the readers of the program's own spans and
counters (``lib/program_spans.py``) take their record from the one kept
in ``tests/data/program_record.json``, recorded on the chip, where a run
takes it from its trace, its tracer and its counters."""

import os

import pytest

RECORD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tests", "data", "program_record.json")


@pytest.fixture(autouse=True)
def recorded_program(monkeypatch):
    from lib import program_spans

    monkeypatch.setattr(program_spans, "SOURCE",
                        lambda ctx: program_spans.from_file(RECORD))
