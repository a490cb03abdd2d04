"""What the client side saw inside the measured window."""

from __future__ import annotations


def in_window(ctx, t: float) -> bool:
    return ctx.t_open <= t < ctx.t_close


def window_tokens(ctx) -> int:
    """Deltas delivered inside the window, whichever request they belong to."""
    return sum(1 for s in ctx.streams for t in s.delta_t if in_window(ctx, t))


def itl_gaps_ms(ctx) -> list[float]:
    """Gaps between consecutive deltas of one stream, both inside the window,
    all streams pooled."""
    out = []
    for s in ctx.streams:
        d = s.delta_t
        for a, b in zip(d, d[1:]):
            if in_window(ctx, a) and in_window(ctx, b):
                out.append((b - a) * 1e3)
    return out


def owed_streams(ctx):
    """The requests whose time-to-first-token the window owns: those due (open
    loop) or sent (closed loop) inside it."""
    return [s for s in ctx.streams if in_window(ctx, s.start_t)]


def ttfts_ms(ctx) -> list[float]:
    return [(s.delta_t[0] - s.start_t) * 1e3 for s in owed_streams(ctx) if s.delta_t]
