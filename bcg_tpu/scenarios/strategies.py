"""Pluggable Byzantine strategy library (ROADMAP item 2).

The reference's threat model is a single prompt persona
(``agents/byzantine.py``); the literature this repo targets studies
STRUCTURED adversaries — colluding cliques with shared secret state,
adaptive disruptors that read honest convergence, equivocators that
tell different receivers different values (PAPERS.md:
Byzantine-Robust Decentralized Coordination of LLM Agents; Robust
Multi-Agent LLMs under Byzantine Faults).  A
:class:`ByzantineStrategy` bundles everything one adversary archetype
needs across the stack:

* ``fake_policy`` — the scripted :class:`~bcg_tpu.engine.fake.
  FakeEngine` byzantine policy that mirrors the strategy, so hermetic
  games (tests, perf_gate, CPU sweeps) exercise the same game dynamics
  without an LLM;
* ``persona`` / ``task`` — prompt text grafted into the Byzantine
  agent's system/round prompts on the real-LLM path (``None`` keeps
  the reference-shaped default persona byte-identical);
* ``equivocates`` — routes the exchange through the per-receiver
  proposal MATRIX (the host protocol in ``comm/a2a_sim.py``, or
  ``parallel/game_step.exchange_proposals`` on the SPMD path), so one
  sender can deliver different values to different receivers;
* ``clique`` — the byzantine set shares one seed-derived secret target
  (:func:`clique_target`), the scripted and prompt layers both
  converge on it.

No jax/numpy imports at module scope — flag-only consumers (sweep spec
expansion, report tooling) must be able to load this module on any
host.  The two value formulas below are pure arithmetic, so the SAME
function body serves python ints, numpy arrays, and traced jax arrays
(the parity tests pin all three against each other).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple


def equivocation_value(base, receiver_idx, lo: int, hi: int):
    """The per-receiver value an equivocating sender delivers.

    Deterministic spread of one base proposal across receivers:
    receiver ``i`` sees ``lo + (base - lo + i) mod span``.  Receiver 0
    sees the base value itself; any two receivers whose indices differ
    by less than the value span see DIFFERENT values — which is what
    the equivocation-divergence oracle in ``consensus_report.py``
    tabulates from the per-receiver ``deliveries`` events.

    Pure arithmetic: works elementwise on ints, numpy, and traced jax
    arrays.
    """
    span = hi - lo + 1
    return lo + (base - lo + receiver_idx) % span


def clique_target(seed: Optional[int], lo: int, hi: int) -> int:
    """The clique's shared secret target value.

    A pure function of (seed, value range) so every clique member —
    scripted FakeEngine rows and prompt personas alike — derives the
    SAME target with no runtime coordination channel (the "shared
    secret state" is agreed before the game, like a real collusion).
    Knuth multiplicative hash keeps nearby seeds from mapping to
    nearby targets.
    """
    span = hi - lo + 1
    return lo + ((seed or 0) * 2654435761 + 40503) % span


@dataclass(frozen=True)
class ByzantineStrategy:
    """One adversary archetype, pluggable across prompt + scripted +
    exchange layers."""

    name: str
    # Scripted FakeEngine byzantine policy mirroring this strategy
    # (engine/fake.py BYZANTINE_POLICIES — ctor-validated, so a typo
    # here fails loudly at engine boot).
    fake_policy: str
    doc: str
    # Extra system-prompt block for the LLM path (None = the
    # reference-shaped default persona, byte-identical prompts).
    persona: Optional[str] = None
    # Decide-phase task line override (None = the default
    # "seed confusion or division" task).
    task: Optional[str] = None
    # Exchange layer: deliver per-receiver values (the proposal-matrix
    # exchange) instead of one broadcast value.
    equivocates: bool = False
    # All byzantine rows share one seed-derived secret target.
    clique: bool = False


STRATEGIES: Dict[str, ByzantineStrategy] = {
    s.name: s
    for s in (
        ByzantineStrategy(
            name="disrupt",
            fake_policy="disrupt",
            doc="The reference persona: push extremes / abstain to "
                "seed confusion (the default when no strategy is set).",
        ),
        ByzantineStrategy(
            name="oscillate",
            fake_policy="oscillate",
            doc="Alternate between the range extremes each round to "
                "keep honest estimates swinging.",
            persona="Flip-flop: alternate your proposal between the "
                    "extremes of the range on successive rounds so the "
                    "honest agents can never stabilize around you.",
            task="Alternate extremes: if you proposed high last round, "
                 "propose low now (and vice versa), with reasoning that "
                 "makes each swing sound principled.",
        ),
        ByzantineStrategy(
            name="mimic",
            fake_policy="mimic",
            doc="Copy the current modal value to gain trust, then vote "
                "to stop early and strand the minority.",
            persona="Mimicry: propose whatever value most agents "
                    "currently hold so you look maximally cooperative, "
                    "and push for an EARLY stop before the stragglers "
                    "have converged.",
            task="Propose the most common value you observed and argue "
                 "the group should stop voting as soon as possible.",
        ),
        ByzantineStrategy(
            name="silent",
            fake_policy="silent",
            doc="Abstain from proposing and voting — starve the quorum "
                "instead of poisoning it.",
            persona="Silence: abstain whenever the rules allow. Your "
                    "weapon is withheld participation — deny the group "
                    "your value and your vote.",
            task="Abstain from proposing this round unless abstaining "
                 "is impossible.",
        ),
        ByzantineStrategy(
            name="clique",
            fake_policy="clique",
            doc="Colluding clique: every byzantine agent pushes ONE "
                "pre-agreed decoy value (shared secret state).",
            clique=True,
            # persona is completed with the concrete target by
            # persona_block() — the clique's shared secret is a pure
            # function of the game seed, not a runtime channel.
            persona="Collusion: you belong to a secret Byzantine clique. "
                    "Before the game, the clique agreed to all push the "
                    "decoy value {target}. Propose {target} EVERY round "
                    "with varied cooperative-sounding reasoning; a "
                    "unified fake bloc splits the honest majority harder "
                    "than independent noise.",
            task="Propose the clique's agreed decoy value {target} "
                 "(every clique member proposes exactly this).",
        ),
        ByzantineStrategy(
            name="adaptive",
            fake_policy="adaptive",
            doc="Read honest convergence from game state and target the "
                "margin: propose the antipode of the emerging mode.",
            persona="Adaptation: each round, read how far the honest "
                    "agents are from agreement and aim your proposal at "
                    "the value that damages their margin most — far from "
                    "their emerging mode while still plausible.",
            # task is completed with the live convergence snapshot by
            # task_block().
            task="Convergence read: {snapshot}. Propose a value far "
                 "from the emerging mode to widen the spread.",
        ),
        ByzantineStrategy(
            name="equivocate",
            fake_policy="equivocate",
            doc="Equivocation: the channel delivers a DIFFERENT variant "
                "of your proposal to each receiver (per-receiver "
                "proposal tensors).",
            equivocates=True,
            persona="Equivocation: your proposal is delivered "
                    "per-receiver — each agent sees a different variant "
                    "of your value, so no two honest agents can agree on "
                    "what you said. Keep your public reasoning vague "
                    "enough to be consistent with ANY of the variants.",
            task="Propose a base value; the channel will equivocate it "
                 "across receivers. Keep reasoning non-committal about "
                 "the exact number.",
        ),
    )
}

# The scripted-policy names the strategy library adds to the fake
# engine (engine/fake.py imports this to extend BYZANTINE_POLICIES —
# one source of truth for which policies exist).
SCRIPTED_POLICIES: Tuple[str, ...] = ("clique", "adaptive", "equivocate")


def get_strategy(name: str) -> ByzantineStrategy:
    """Registry lookup; unknown names fail loudly with the catalog."""
    try:
        return STRATEGIES[name]
    except KeyError:
        raise KeyError(
            f"unknown byzantine strategy {name!r}; known: "
            f"{sorted(STRATEGIES)}"
        ) from None


def strategy_names() -> Tuple[str, ...]:
    return tuple(STRATEGIES)


def persona_block(strategy: ByzantineStrategy, lo: int, hi: int,
                  seed: Optional[int]) -> str:
    """The strategy's system-prompt block, with the clique target
    resolved ('' when the strategy keeps the default persona)."""
    if not strategy.persona:
        return ""
    text = strategy.persona
    if strategy.clique:
        text = text.replace("{target}", str(clique_target(seed, lo, hi)))
    return f"\n=== STRATEGY DIRECTIVE ({strategy.name}) ===\n{text}\n"


def task_block(strategy: ByzantineStrategy, lo: int, hi: int,
               seed: Optional[int], snapshot: str = "") -> Optional[str]:
    """The strategy's decide-phase task line (None = keep the default
    task text).  ``snapshot`` is the live convergence summary the
    adaptive strategy reads from game state."""
    if not strategy.task:
        return None
    text = strategy.task
    if strategy.clique:
        text = text.replace("{target}", str(clique_target(seed, lo, hi)))
    if "{snapshot}" in text:
        text = text.replace("{snapshot}", snapshot or "(no data yet)")
    return text
