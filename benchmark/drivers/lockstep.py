"""``mode: lockstep``: each game in flight plays its round on its own,
one after the other, through ``BCGSimulation.run_round``."""

from __future__ import annotations

from lib import spans
from lib.system import NotKept


class Driver:
    """The cell's games in flight and how to play one round of them all."""

    def __init__(self, system):
        self.system = system
        self.n = system.traffic["games"]
        agents = system.traffic["num_honest"] + system.traffic["num_byzantine"]
        # decide and vote rows, each counted once, retries not again
        self.decisions_per_game_round = 2 * agents
        self.stopped = None      # why the last round played was stopped early, if it was

    def draw(self) -> dict:
        """The next round of the seed's stream: its games and the
        sampling state it starts from."""
        return {"games": [self.system.next_fitting() for _ in range(self.n)],
                "key": self.system.sampling_state()}

    def play(self, recipe: dict, screening: bool = False) -> list:
        """One round from a recipe; returns the engine calls it made.
        With ``screening`` the round stops at a call that shows set-up
        will not keep it (a retry's further call, vote prompts off the
        band); ``self.stopped`` then says why."""
        system = self.system
        sims = [system.game(k) for k in recipe["games"]]
        system.restore_sampling_state(recipe["key"])
        first = len(system.calls)
        self.stopped, system.screening = None, set() if screening else None
        try:
            with spans.span("bench.round"):
                for sim in sims:
                    sim.run_round()
        except NotKept as e:
            self.stopped = e.why
            system.log(f"round of games {recipe['games']} stopped at {e}")
        finally:
            system.screening = None
        return system.calls[first:]

    def _declared_work(self, calls: list) -> bool:
        """Every declared kind once per game, each for a declared count
        of decode steps.  More calls are a retry, and a decide call that
        stops short of its budget (every row closed its answer early:
        one round in some thirty at 8B) is a fifth less work.  The
        declared calls at another shape than declared, or off their
        rung, are a traffic file that does not describe its games: an
        error."""
        decl = self.system.traffic["calls"]
        if sorted(c.kind for c in calls) != sorted(list(decl) * self.n) or \
                not all(self.system.steps_declared(c) for c in calls):
            return False
        self.system.check_declared(calls, band=False)
        return True

    def off_band(self, calls: list) -> bool:
        """The declared work on the declared rungs, but a call's longest
        prompt off its kind's band: another count of prefill chunk
        programs than the cell's windows send.  The seed's model and the
        game decide that, not the file: passed over, and counted apart
        from retries."""
        return self._declared_work(calls) and \
            not all(self.system.on_band(c) for c in calls)

    def clean(self, calls: list) -> bool:
        """Did the round run exactly the declared work, every call on
        its band?  If not it is passed over."""
        return self._declared_work(calls) and \
            all(self.system.on_band(c) for c in calls)
