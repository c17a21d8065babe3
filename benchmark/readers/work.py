"""What the window's calls needed, from the recorded calls and the cost
functions: shared by the share-of-peak readers."""

from lib import costs


def passes(call) -> list:
    """Forward passes each row needed in the decode loop: its served
    tokens (EOS included where it came) less the one sampled from the
    prefill's logits."""
    return [max(0, min(len(t) + 1, b) - 1) for t, b in zip(call.texts, call.budgets)]


def prefill_flops(ctx) -> int:
    return sum(costs.prefill_flops(ctx["config"], c.prompt_lens) for c in ctx["calls"])


def decode_flops(ctx) -> int:
    return sum(costs.decode_flops(ctx["config"], c.prompt_lens, passes(c))
               for c in ctx["calls"])


def decode_bytes(ctx) -> int:
    cfg = ctx["config"]
    return sum(costs.decode_bytes(cfg, cfg["weight_dtype"], cfg["kv_dtype"], c.steps,
                                  c.prompt_lens, passes(c)) for c in ctx["calls"])


def _kernel_total(needs) -> dict:
    needs = list(needs)
    return {"flops": sum(n["flops"] for n in needs), "bytes": sum(n["bytes"] for n in needs)}


def flash_prefill_kernel(ctx) -> dict:
    return _kernel_total(costs.flash_prefill_kernel(ctx["config"], c.prompt_lens)
                         for c in ctx["calls"])


def decode_attention_kernel(ctx) -> dict:
    cfg = ctx["config"]
    return _kernel_total(
        costs.decode_attention_kernel(cfg, cfg["kv_dtype"], c.prompt_lens, passes(c))
        for c in ctx["calls"])
