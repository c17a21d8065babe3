"""Set-up's rounds and the measured window: whole rounds of the cell's
games.

A *round* of a cell is one game round of each of its ``games`` in
flight, every game at its first round (a random model ends most games
after one round, and a second round's prompts fall on another rung).

The work of a window must not depend on the seed, and the window may
compile nothing.  A round in which a row comes back invalid makes the
game's retry ladder send more calls, single rows among them, at shapes
no traffic file can know beforehand.  So set-up draws rounds from the
seed's stream and plays each once, which is also the warm-up: a round
that ran exactly the declared calls, each for a declared count of decode
steps and on its band of prompt lengths, is *proved*; one that retried
is passed over, like a game whose prompts fall off the declared rungs
("choose traffic on which no operation fails"), and so is one whose
vote prompts fell off the band (another count of prefill chunk
programs), and set-up goes on with the next model of the seed's stream.
The window then plays the proved rounds
(``distinct_rounds`` of them, of successive games of the stream) again,
in turn, each from the same games and the same
sampling key: the same prompts through the same programs give the same
tokens, so no row can come back invalid and every window of a cell is
the same number of calls of the same shapes.  It opens and closes on
whole rounds: as many as end within ``--seconds``, and the first in any
case.

Set-up, as ``setup_s`` counts it, ends with the first round the process
plays to its end, kept or not: by then every declared shape is compiled
or loaded and the first decisions are made, which is what a user's fresh
process pays.  The rounds after it prove and screen for the window:
they are the harness's own, differ in number from seed to seed, and are
counted (``rounds_passed_over``, ``rounds_off_band``), not timed as
set-up.
"""

from __future__ import annotations

import importlib
import time

from . import spans
from .system import System, UndeclaredWork


def driver_for(system: System):
    """The driver of the traffic file's ``mode`` (``drivers/<mode>.py``)."""
    return importlib.import_module("drivers." + system.traffic["mode"]).Driver(system)


_REDRAW = 7919      # the next model of a seed's stream: weights from seed + _REDRAW
_MOST_ROUNDS = 40   # set-up gives up on a seed after playing this many


def warm_up(games) -> dict:
    """Set-up's rounds: draw and play until ``distinct_rounds`` rounds
    are proved on one model.  The first play compiles (or loads) the
    declared shapes, and ``setup_s`` ends with it (``setup_end``, with
    ``compile_s`` the backend's compile seconds up to there); should a
    round that is kept still compile, set-up runs to that round's end.

    A round is not kept if it retried, stopped short of the declared
    decode steps, or sent a call's prompts off the band.  Each of the
    three is a property of the seed's random model far more than of the
    game: a model whose rows run their first string to the budget sends
    the shortest vote prompts in every round (seed 2202000011: 9 rounds
    of 9 under the band), one whose greedy row the game's validity check
    refuses retries in every round (seed 1800104729: 14 of 14), one that
    closes every answer early stops short in every round.  So a round
    that is not kept sends set-up to the next model of the seed's stream
    (weights from ``weights_seed + _REDRAW``; the reference follows), as
    a game off the declared rungs sends it to the next game; rounds
    proved on the model before are dropped.  After the first round,
    which has to run whole, a round stops at the call that shows it will
    not be kept (``games.play(..., screening=True)``): screening is the
    harness's own cost and no user's.  What set-up played is dropped
    from the record."""
    system = games.system
    want = system.traffic["distinct_rounds"]
    games.proved, passed_over, off_band, redrawn = [], 0, 0, 0
    setup_end = compile_s = None
    for _ in range(_MOST_ROUNDS):
        if len(games.proved) == want:
            break
        recipe = games.draw()
        failed0, compiled = system.engine.failed_rows, system.compiles.snapshot()
        calls = games.play(recipe, screening=setup_end is not None)
        failed = system.engine.failed_rows - failed0
        kept = games.stopped is None and games.clean(calls) and not failed
        if setup_end is None or (kept and system.compiles.snapshot() != compiled):
            if setup_end is not None:
                system.log("a round that is kept compiled: set-up runs to its end")
            setup_end, compile_s = time.perf_counter(), system.compiles.backend_s
        ran = [(c.kind, c.rows, max(c.prompt_lens), c.steps) for c in calls]
        if kept:
            system.log(f"round of games {recipe['games']} ran {ran}: proved")
            recipe["texts"] = [list(c.texts) for c in calls]
            recipe["calls"] = calls
            games.proved.append(recipe)
            continue
        if games.stopped == "off_band" or (
                games.stopped is None and not failed and games.off_band(calls)):
            off_band += 1
            why = "off the band"
        else:
            passed_over += 1
            why = f"retried or stopped short, {failed} failed rows"
        passed_over += len(games.proved)
        games.proved = []
        redrawn += 1
        system.log(f"round of games {recipe['games']} ran {ran}: {why}, passed over; "
                   f"weights redrawn from {system.weights_seed + _REDRAW}")
        system.remake_weights(system.weights_seed + _REDRAW)
    if len(games.proved) < want:
        raise RuntimeError("the seed's stream gave no round of the declared work")
    system.calls.clear()
    spans.RECORDED.clear()
    return {"rounds_proved": len(games.proved), "rounds_passed_over": passed_over,
            "rounds_off_band": off_band, "models_redrawn": redrawn,
            "setup_end": setup_end, "compile_s": compile_s}


def measure(games, seconds: float) -> dict:
    """Play proved rounds, in turn, for about ``seconds``; a further
    round starts only if, at the pace of the last, it would end inside
    the budget.  A window that compiles, or runs a shape the traffic
    file does not declare, is no measurement."""
    system = games.system
    engine = system.engine
    before = system.compiles.snapshot()
    rows0, failed0 = engine.total_rows, engine.failed_rows
    game_rounds = mismatches = 0
    t0 = time.perf_counter()
    while True:
        r0 = time.perf_counter()
        recipe = games.proved[(game_rounds // games.n) % len(games.proved)]
        calls = games.play(recipe)
        game_rounds += games.n
        if [list(c.texts) for c in calls] != recipe["texts"]:
            mismatches += 1
        now = time.perf_counter()
        if (now - t0) + (now - r0) > seconds:
            break
    elapsed = time.perf_counter() - t0
    system.check_declared()
    if system.compiles.snapshot() != before:
        raise UndeclaredWork(
            f"the window compiled: programs/cache hits/misses {before} -> "
            f"{system.compiles.snapshot()}")
    if mismatches:
        system.log(f"{mismatches} replayed round(s) served other tokens than when proved")
    return {
        "seconds": elapsed,
        "game_rounds": game_rounds,
        "decisions": games.decisions_per_game_round * game_rounds,
        "games_in_flight": games.n,
        "rows": engine.total_rows - rows0,
        "failed": engine.failed_rows - failed0,
        "replay_mismatches": mismatches,
    }
