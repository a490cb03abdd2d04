"""Process start to window open: weights, engine, warm-up (compilation in a
first run), and bringing the load to its steady state."""


def read(ctx):
    return ctx.setup_s
