"""Helpers shared by the per-layer metric readers."""
from __future__ import annotations


def span_durations(ctx, name: str) -> list:
    """Durations (seconds) of the program's ``name`` spans in the window."""
    if ctx.spans is None:
        return []
    return [s[2] * 1e-9 for s in ctx.spans if s[0] == name]


def program_mean_ms(ctx, program: str):
    """Mean device milliseconds of one program's events in the trace."""
    if ctx.trace is None:
        return None
    n, secs = ctx.trace["programs"].get(program, (0, 0.0))
    return 1e3 * secs / n if n else None
