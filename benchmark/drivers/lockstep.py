"""``mode: lockstep``: each game in flight plays its round on its own,
one after the other, through ``BCGSimulation.run_round``."""

from __future__ import annotations

from lib import spans


class Driver:
    """The cell's games in flight and how to play one round of them all."""

    def __init__(self, system):
        self.system = system
        self.n = system.traffic["games"]
        agents = system.traffic["num_honest"] + system.traffic["num_byzantine"]
        # decide and vote rows, each counted once, retries not again
        self.decisions_per_game_round = 2 * agents

    def draw(self) -> dict:
        """The next round of the seed's stream: its games and the
        sampling state it starts from."""
        return {"games": [self.system.next_fitting() for _ in range(self.n)],
                "key": self.system.sampling_state()}

    def play(self, recipe: dict) -> list:
        """One round from a recipe; returns the engine calls it made."""
        system = self.system
        sims = [system.game(k) for k in recipe["games"]]
        system.restore_sampling_state(recipe["key"])
        first = len(system.calls)
        with spans.span("bench.round"):
            for sim in sims:
                sim.run_round()
        return system.calls[first:]

    def _declared_kinds(self, calls: list) -> bool:
        decl = self.system.traffic["calls"]
        return sorted(c.kind for c in calls) == sorted(list(decl) * self.n)

    def stopped_short(self, calls: list) -> bool:
        """The declared calls and no other, but not for the declared
        count of decode steps."""
        return self._declared_kinds(calls) and \
            not all(self.system.steps_declared(c) for c in calls)

    def clean(self, calls: list) -> bool:
        """Did the round run exactly the declared work: every declared
        kind once per game, each for a declared count of decode steps?
        More calls are a retry, and a decide call that stops short of its
        budget (every row closed its answer early: one round in some
        thirty at 8B) is a fifth less work: not clean, passed over.  The
        declared calls at another shape than declared are no retry but a
        traffic file that does not describe its games: an error."""
        if not self._declared_kinds(calls) or self.stopped_short(calls):
            return False
        self.system.check_declared(calls)
        return True
