"""Set-up: from process start to the first timed step (import, token
shards, state on the device, compile or cache load, the three checked
steps, warm-up). The correctness check's own reading is left out."""


def read(ctx):
    return ctx.setup_s
