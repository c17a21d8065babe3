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


# ------------------------------------------------------------ the band

def _short(traffic: dict) -> dict:
    """The tiny mix with a 100-token decide budget and one proved round:
    rounds of a third of the time, for cases that play many."""
    traffic = json.loads(json.dumps(traffic))
    traffic["calls"]["decide"].update(max_tokens=100, decode_steps=[1, 99])
    traffic["distinct_rounds"] = 1
    return traffic


BAND_SEED = 77


@pytest.fixture(scope="module")
def vote_lengths():
    """The longest vote prompt of each round that set-up would play if
    it kept none: game k on the k-th model of the seed's stream."""
    from lib import system, window

    _, _, config, traffic = _files()
    sysm = system.System(config, _short(traffic), BAND_SEED)
    try:
        games = window.driver_for(sysm)
        lengths = []
        for _ in range(4):
            lengths += [max(c.prompt_lens) for c in games.play(games.draw())
                        if c.kind == "vote"]
            sysm.remake_weights(sysm.weights_seed + window._REDRAW)
        return lengths
    finally:
        sysm.close()


@pytest.mark.parametrize("first_on_band", [True, False])
def test_set_up_passes_over_off_band_rounds_and_ends_with_the_first(
        vote_lengths, first_on_band, monkeypatch):
    """Set-up passes over a round whose vote prompts are off the band,
    goes on with the seed's next model and still proves a round;
    ``setup_s`` ends with the first round played, on the band or off it,
    and what is played after it is not in it."""
    import time

    import run

    # the band holds one round's length alone: the first, or the first later
    # one that differs from all before it
    k = 0 if first_on_band else next(
        (i for i, n in enumerate(vote_lengths) if i and n not in vote_lengths[:i]), None)
    if k is None:
        pytest.skip(f"no later round stands apart in {vote_lengths}")
    bench, cell, config, traffic = _files()
    traffic = _short(traffic)
    traffic["calls"]["vote"]["prompt_band"] = [vote_lengths[k] - 1, vote_lengths[k]]
    logged = []
    monkeypatch.setattr(run, "log", lambda m: logged.append((time.perf_counter(), m)))
    out = run.run_cell(bench, cell, config, traffic, BAND_SEED, 3.0, False, DEVICE)
    # (``correct`` is not this case's: the limits were read at the 300-token budget)
    assert out["failed"] == 0 and out["attempted"] >= 8
    passed = [m for _t, m in logged if "off the band, passed over" in m]
    assert len(passed) == k
    stopped = [m for _t, m in logged if "stopped at a vote call" in m]
    assert len(stopped) == max(0, k - 1)            # the first round runs whole
    at, done = next((t, m) for t, m in logged if m.startswith("set-up done"))
    assert f"0 round(s) passed over and {k} off the band" in done
    setup_s = out["metrics"]["setup_s"]["value"]
    if k:
        # logged right after the clock was read at the first round's end
        first_end = next(t for t, m in logged if "passed over" in m)
        assert first_end - run.T_START == pytest.approx(setup_s, abs=1.0)
        assert at - run.T_START - setup_s > 1.0 * k
    else:
        assert at - run.T_START == pytest.approx(setup_s, abs=1.0)


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
