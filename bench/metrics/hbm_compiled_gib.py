"""Device memory of the compiled step, per device as the compiler lays it
out (argument + output - alias + temporaries): what bounds the batch. It
moves tokens_per_s only through the batch that freed memory buys."""


def read(ctx):
    m = ctx.memory
    if m is None:
        return None
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return total / 2 ** 30
