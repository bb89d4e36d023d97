"""mfu (%): model FLOPs per token, as the cell's reference counts them
(``flops_per_token`` of the module its configuration's ``reference`` key
names; qwen2's is PaLM app. B's 6 N + 12 L H Q T), times the traced run's
tokens/s, over chips x the bf16 peak of the device kind."""


def read(ctx):
    return 100.0 * ctx.flops_per_token * ctx.tokens_per_s / (
        ctx.chips * ctx.peak["bf16_flop_per_s"])
