"""The whole step's share of the chips' bf16 peak over the window:
tokens_per_s x model FLOPs per token (``bench/flops.py``) / (chips x the
peak of ``bench/peaks.json`` for the device kind)."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    per_token = ctx.flops_step / ctx.cell.tokens
    rate = ctx.tokens / ctx.window_s
    return 100.0 * rate * per_token / (ctx.chips
                                       * ctx.peak["bf16_flops_per_s"])
