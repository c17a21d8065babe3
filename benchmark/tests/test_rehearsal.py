"""A CPU rehearsal of the whole harness at ``bcg-tpu/tiny-test``: the
functions the command drives, with the look for a chip skipped; the
command itself, which must refuse to run here; and the timed path broken
underneath, which must come out as not correct."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = json.load(open(os.path.join(HERE, "configs", "tiny.json")))
    traffic = json.load(open(os.path.join(HERE, "traffic", "tiny-lockstep.json")))
    cell = {"name": "tiny.tiny-lockstep", "config": "tiny",
            "traffic": "tiny-lockstep", "chips": 1}
    return bench, cell, config, traffic


@pytest.fixture(scope="module")
def sound():
    import run

    bench, cell, config, traffic = _files()
    return run.run_cell(bench, cell, config, traffic, 2147404729, 6.0, False, DEVICE)


def test_last_line_shape(sound):
    assert list(sound)[:4] == ["correct", "attempted", "failed", "metrics"]
    assert list(sound)[-1] == "compared"
    assert set(sound["metrics"]) == {"decisions_per_s", "round_s", "setup_s"}
    for m in sound["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert sound["device"]["platform"] == "cpu"
    assert json.loads(json.dumps(sound)) == sound


def test_sound_run_is_correct_and_whole_rounds(sound):
    assert sound["correct"] is True, sound["compared"]
    assert sound["failed"] == 0
    # 4 agents x (decide + vote) rows a round, whole rounds only
    assert sound["attempted"] % 8 == 0 and sound["attempted"] >= 8
    m = sound["metrics"]
    rounds = sound["attempted"] // 8
    assert m["decisions_per_s"]["value"] * m["round_s"]["value"] == pytest.approx(8.0)
    assert rounds >= 1
    for name, row in sound["compared"].items():
        assert row["value"] <= row["limit"], name


def test_altered_token_is_not_correct():
    """A served character altered where it is produced: the reference
    puts a better token there, and ``correct`` comes out false."""
    import run
    from tools.limits import alter_one_character

    bench, cell, config, traffic = _files()
    out = run.run_cell(bench, cell, config, traffic, 2147404729, 6.0, False, DEVICE,
                       fault=alter_one_character)
    assert out["correct"] is False
    gap = out["compared"]["greedy_gap_max"]
    assert gap["value"] > gap["limit"]


def test_undeclared_shape_fails_the_run():
    import run
    from lib.system import UndeclaredWork

    bench, cell, config, traffic = _files()
    traffic = json.loads(json.dumps(traffic))
    traffic["calls"]["vote"]["prompt_rung"] = 1024     # not what the games run
    with pytest.raises(UndeclaredWork):
        run.run_cell(bench, cell, config, traffic, 2147404729, 6.0, False, DEVICE)


def test_redraw_onto_declared_rungs():
    from lib import system

    _, _, config, traffic = _files()
    traffic = json.loads(json.dumps(traffic))
    traffic["calls"]["decide"]["prompt_rung_below"] = 10 ** 6   # nothing fits
    sysm = system.System(config, traffic, 5)
    try:
        with pytest.raises(RuntimeError, match="fits the declared rungs"):
            sysm.next_fitting()
    finally:
        sysm.close()


def test_command_refuses_to_run_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "qwen3-8b-int8.lockstep", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
