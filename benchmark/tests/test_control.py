"""The control, at a size a test run can hold: the reference put in the
program's place at the precision below the stated one (int4-rounded
weights for W8) has to come out as NOT correct, on the same rows on
which the program's own sound run is correct.

The rows are read as ``tools/limits.py`` reads them on the chip: the
program's own rounds through the timed path, two proved rounds a seed.
On the chip at the cell's own size the control is read anew by
``tools/limits.py`` (PERF.md section 6)."""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
SEEDS = [2147404729, 2147509458, 2147614187]


@pytest.fixture(scope="module")
def played():
    from tools.limits import play_seeds

    config = json.load(open(os.path.join(HERE, "configs", "tiny.json")))
    traffic = json.load(open(os.path.join(HERE, "traffic", "tiny-lockstep.json")))
    return config, traffic, play_seeds(config, traffic, SEEDS, 0)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(played, seed):
    from lib import correct

    config, traffic, rows = played
    limits = config["limits"]
    sample = rows[seed]["sound"]
    weights_seed = rows[seed]["weights_seed"]
    table = correct.positions(config, traffic, weights_seed, sample)
    sound = dict(correct.numbers(table), invalid_rows=rows[seed]["invalid"], failed_rows=0)
    ok, compared = correct.verdict(sound, limits)
    assert ok, compared                                # the program: sound
    # the control in the program's place, at the same positions
    low = correct.positions(config, traffic, weights_seed, sample,
                            config["control"]["weights"])
    control = dict(correct.numbers(correct.as_control(table, low, seed)),
                   invalid_rows=0, failed_rows=0)
    ok, compared = correct.verdict(control, limits)
    assert ok is False, compared
    failed = [n for n, row in compared.items() if row["value"] > row["limit"]]
    assert failed and set(failed) <= {"greedy_gap_max", "served_histogram_chi2"}
