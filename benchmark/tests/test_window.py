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
        weights_seed=5, remade=[], log=lambda m: None)
    system.steps_declared = lambda call: System.steps_declared(system, call)
    system.check_declared = lambda calls: System.check_declared(system, calls)

    def remake_weights(weights_seed):
        system.remade.append(weights_seed)
        system.weights_seed = weights_seed

    system.remake_weights = remake_weights
    return system


def _call(kind, steps, rows=10, longest=2190):
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


def test_set_up_passes_over_a_retry_and_redraws_a_model_that_cannot_be_proved():
    from drivers.lockstep import Driver
    from lib import window

    system = _system()
    driver = Driver(system)
    retried = [_call("decide", 299), _call("decide", 110, rows=1), _call("vote", 20)]
    script = iter([
        [_call("decide", 299), _call("vote", 21)],                     # proved
        [_call("decide", 193), _call("vote", 22)],                     # short: redraw
        retried,                                                       # passed over
        [_call("decide", 299), _call("vote", 23)],                     # proved
        retried, retried,                                              # twice in a row: redraw
        [_call("decide", 299), _call("vote", 23)],                     # proved
        [_call("decide", 299), _call("vote", 19)],                     # proved
    ])
    driver.draw = lambda: {"games": [0], "key": None}
    driver.play = lambda recipe: next(script)
    out = window.warm_up(driver)
    assert system.remade == [5 + window._REDRAW, 5 + 2 * window._REDRAW]
    # a round proved on a model is dropped with it
    assert out == {"rounds_proved": 2, "rounds_passed_over": 6, "models_redrawn": 2}
    assert [c.steps for r in driver.proved for c in r["calls"]] == [299, 23, 299, 19]
