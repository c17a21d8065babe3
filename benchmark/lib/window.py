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
steps, is *proved*; one that retried is passed over, like a game whose
prompts fall off the declared rungs ("choose traffic on which no
operation fails").  The window then plays the proved rounds
(``distinct_rounds`` of them, of successive games of the stream) again,
in turn, each from the same games and the same
sampling key: the same prompts through the same programs give the same
tokens, so no row can come back invalid and every window of a cell is
the same number of calls of the same shapes.  It opens and closes on
whole rounds: as many as end within ``--seconds``, and the first in any
case.
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


def warm_up(games) -> dict:
    """Set-up's rounds: draw and play until ``distinct_rounds`` rounds
    are proved.  The first play compiles (or loads) the declared shapes.
    A round that retried is passed over (one in 8 to 37 at 8B).  Two
    things are properties of the seed's random model and not of a round,
    and send set-up to the next model of the seed's stream, as a game
    off the declared rungs sends it to the next game: a round that
    stopped short of the declared decode steps (every row closes its
    answer before the budget: one seed in some sixty, a fifth less work
    a round), and a second round in a row that retried (a greedy row,
    the same text every round, that the game's validity check refuses:
    seed 1800104729 retried in 14 rounds of 14).  Rounds proved on the
    model before are dropped.  What set-up played is dropped from the
    record."""
    system = games.system
    want = system.traffic["distinct_rounds"]
    failed0 = system.engine.failed_rows
    games.proved, passed_over, redrawn, in_a_row = [], 0, 0, 0
    for _ in range(want + 12):
        if len(games.proved) == want:
            break
        recipe = games.draw()
        calls = games.play(recipe)
        if games.clean(calls) and system.engine.failed_rows == failed0:
            recipe["texts"] = [list(c.texts) for c in calls]
            recipe["calls"] = calls
            games.proved.append(recipe)
            in_a_row = 0
            continue
        passed_over += 1
        in_a_row += 1
        system.log(f"round of games {recipe['games']} ran "
                   f"{[(c.kind, c.rows, c.steps) for c in calls]}, "
                   f"{system.engine.failed_rows - failed0} failed rows: passed over")
        failed0 = system.engine.failed_rows
        if games.stopped_short(calls) or in_a_row == 2:
            redrawn += 1
            passed_over += len(games.proved)
            games.proved, in_a_row = [], 0
            system.remake_weights(system.weights_seed + _REDRAW)
            system.log(f"the seed's model stops short or retries round after round: "
                       f"weights redrawn from {system.weights_seed}")
    if len(games.proved) < want:
        raise RuntimeError("the seed's stream gave no round of the declared work")
    system.calls.clear()
    spans.RECORDED.clear()
    return {"rounds_proved": len(games.proved), "rounds_passed_over": passed_over,
            "models_redrawn": redrawn}


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
