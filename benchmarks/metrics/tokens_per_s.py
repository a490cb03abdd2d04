"""Output tokens delivered to clients inside the window, over the window."""
from harness.window import window_tokens


def read(ctx):
    return window_tokens(ctx) / ctx.seconds
