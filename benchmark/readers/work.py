"""What the window's calls needed: each recorded call, handed to the
function of the configuration's cost model (``costs/<name>.py``) that a
metric's file names, summed over the calls.  Nothing here is a model's."""

import collections

from lib import costs

Recorded = collections.namedtuple("Recorded", "prompt_lens passes steps")


def passes(call) -> list:
    """Forward passes each row needed in the decode loop: its served
    tokens (EOS included where it came) less the one sampled from the
    prefill's logits."""
    return [max(0, min(len(t) + 1, b) - 1) for t, b in zip(call.texts, call.budgets)]


def recorded(call) -> Recorded:
    """One engine call as every cost function takes it."""
    return Recorded(list(call.prompt_lens), passes(call), call.steps)


def total(ctx, name):
    """The cost model's function ``name`` over the window's calls: a
    number, or a kernel's ``flops`` and ``bytes``, summed."""
    fn = getattr(costs.module_for(ctx["config"]), name)
    needs = [fn(ctx["config"], recorded(c)) for c in ctx["calls"]]
    if needs and isinstance(needs[0], dict):
        return {k: sum(n[k] for n in needs) for k in needs[0]}
    return sum(needs)
