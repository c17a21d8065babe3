"""Set-up's proving of rounds, with the engine stood in for by plain
objects: which rounds the lockstep driver keeps, and what set-up does
with a seed whose model closes every answer early."""

import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _system():
    from lib.system import System

    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "lockstep.json")))
    system = types.SimpleNamespace(
        traffic=traffic, calls=[], engine=types.SimpleNamespace(failed_rows=0),
        weights_seed=5, remade=[], log=lambda m: None,
        compiles=types.SimpleNamespace(snapshot=lambda: (0, 0, 0), backend_s=1.5))
    system.steps_declared = lambda call: System.steps_declared(system, call)
    system._on_band = lambda kind, longest: System._on_band(system, kind, longest)
    system.on_band = lambda call: System.on_band(system, call)
    system.check_declared = lambda calls, band=True: System.check_declared(system, calls, band)

    def remake_weights(weights_seed):
        system.remade.append(weights_seed)
        system.weights_seed = weights_seed

    system.remake_weights = remake_weights
    return system


def _call(kind, steps, rows=10, longest=None):
    """A decide call's longest prompt is 2,190 tokens on every seed; a
    vote call's is the model's and the game's: 2,300 is on the traffic
    file's band."""
    longest = longest or {"decide": 2190, "vote": 2300}[kind]
    return types.SimpleNamespace(kind=kind, rows=rows, steps=steps, texts=["{}"] * rows,
                                 prompt_lens=[longest] * rows)


def test_a_round_that_stops_short_or_retries_is_not_clean():
    """The lockstep driver keeps a round only if it ran the declared
    calls for a declared count of decode steps."""
    from drivers.lockstep import Driver

    driver, call = Driver(_system()), _call
    assert driver.clean([call("decide", 299), call("vote", 23)])
    assert not driver.clean([call("decide", 84), call("vote", 23)])      # stopped short
    assert not driver.clean([call("decide", 299), call("decide", 299, rows=1),
                             call("vote", 23)])                           # a retry
    from lib.system import UndeclaredWork
    with pytest.raises(UndeclaredWork):
        driver.clean([call("decide", 299, longest=5000), call("vote", 23)])


@pytest.mark.parametrize("longest,on", [(2049, True), (2560, True), (2561, False),
                                        (3072, False), (4096, False)])
def test_a_vote_call_off_the_band_is_passed_over_not_an_error(longest, on):
    """On the rung but off the band is the game's doing: not clean, and
    told apart from a retry; off the rung is the file's fault.  A window
    that ran such a call is no measurement."""
    from drivers.lockstep import Driver
    from lib.system import UndeclaredWork

    system = _system()
    driver = Driver(system)
    calls = [_call("decide", 299), _call("vote", 23, longest=longest)]
    assert driver.clean(calls) is on and driver.off_band(calls) is (not on)
    retried = [calls[0], _call("decide", 110, rows=1), calls[1]]
    assert not driver.clean(retried) and not driver.off_band(retried)
    if on:
        system.check_declared(calls)
    else:
        with pytest.raises(UndeclaredWork, match="band"):
            system.check_declared(calls)
    with pytest.raises(UndeclaredWork):
        driver.off_band([_call("decide", 299), _call("vote", 23, longest=4097)])


def _scripted(driver, rounds, monkeypatch=None):
    """Stand the games in for by a script of rounds: each a list of
    calls, or ``(calls so far, why)`` for one that screening stops."""
    from lib import window

    script = iter(rounds)
    asked = []

    def play(recipe, screening=False):
        asked.append(screening)
        item = next(script)
        calls, driver.stopped = item if isinstance(item, tuple) else (item, None)
        return calls

    driver.draw = lambda: {"games": [0], "key": None}
    driver.play = play
    if monkeypatch is not None:
        clock = iter(range(100, 200))
        monkeypatch.setattr(window.time, "perf_counter", lambda: float(next(clock)))
    return script, asked


def test_a_round_not_kept_sends_set_up_to_the_seeds_next_model():
    """Retried, stopped short or off the band: each is the model's doing
    far more than the game's, so the model is redrawn and what was
    proved on it is dropped (two distinct rounds asked for here)."""
    from drivers.lockstep import Driver
    from lib import window

    system = _system()
    system.traffic = dict(system.traffic, distinct_rounds=2)
    driver = Driver(system)
    retried = ([_call("decide", 299)], "retried")              # stopped at the B=1 call
    _scripted(driver, [
        [_call("decide", 299), _call("vote", 21)],                     # proved
        [_call("decide", 193), _call("vote", 22)],                     # short: redraw, drop
        retried,                                                       # redraw
        [_call("decide", 299), _call("vote", 23)],                     # proved
        ([_call("decide", 299)], "off_band"),                          # redraw, drop
        [_call("decide", 299), _call("vote", 23)],                     # proved
        [_call("decide", 299), _call("vote", 19)],                     # proved
    ])
    out = window.warm_up(driver)
    assert system.remade == [5 + window._REDRAW * k for k in (1, 2, 3)]
    # a round proved on a model is dropped with it, and counted as passed over
    assert {k: out[k] for k in ("rounds_proved", "rounds_passed_over", "rounds_off_band",
                                "models_redrawn")} == {
        "rounds_proved": 2, "rounds_passed_over": 4, "rounds_off_band": 1,
        "models_redrawn": 3}
    assert [c.steps for r in driver.proved for c in r["calls"]] == [299, 23, 299, 19]


@pytest.mark.parametrize("first_on_band", [True, False])
def test_set_up_ends_with_the_first_round_and_off_band_rounds_are_counted_apart(
        first_on_band, monkeypatch):
    """``setup_end`` is the clock at the end of the first round played,
    kept or not; the rounds after it move it only if a kept one still
    compiled.  The first round runs whole; the later ones are screened."""
    from drivers.lockstep import Driver
    from lib import window

    system = _system()
    driver = Driver(system)
    on = [_call("decide", 299), _call("vote", 23)]
    off = [_call("decide", 299), _call("vote", 23, longest=2800)]    # whole: the first round
    cut = ([_call("decide", 299)], "off_band")
    rounds = [on] if first_on_band else [off, cut, ([_call("decide", 299)], "retried"), on]
    script, asked = _scripted(driver, rounds + [on], monkeypatch)
    out = window.warm_up(driver)
    assert out["setup_end"] == 100.0 and out["compile_s"] == 1.5
    assert out["rounds_off_band"] == (0 if first_on_band else 2)
    assert out["rounds_passed_over"] == (0 if first_on_band else 1)
    assert len(system.remade) == out["models_redrawn"] == (0 if first_on_band else 3)
    assert asked == [False] + [True] * (len(rounds) - 1)
    assert len(driver.proved) == 1 and len(list(script)) == 1


def test_a_kept_round_that_compiles_extends_set_up(monkeypatch):
    from drivers.lockstep import Driver
    from lib import window

    system = _system()
    driver = Driver(system)
    # before the first play (which ends set-up whatever it compiled), then
    # before and after the second: it is kept, and compiled a program
    programs = iter([(1, 0, 0), (5, 0, 0), (6, 0, 0)])
    system.compiles.snapshot = lambda: next(programs)
    _scripted(driver, [[_call("decide", 299), _call("decide", 110, rows=1), _call("vote", 20)],
                       [_call("decide", 299), _call("vote", 23)]], monkeypatch)
    assert window.warm_up(driver)["setup_end"] == 101.0
