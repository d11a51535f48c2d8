"""Host time per step to read the step's batch through the program's
``ShardedDataLoader`` and ``device_put`` it: the harness's own host-clock
spans (``bench.input``), averaged over the traced steps."""


def read(ctx):
    spans = ctx.host_spans.get("bench.input", [])
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
