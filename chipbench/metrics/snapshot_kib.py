"""Journal: mean size of a snapshot row, the ``bytes`` arg of the
``journal/snapshot`` spans, in KiB."""


def read(ctx):
    if ctx.spans is None:
        return None
    sizes = [s[3]["bytes"] for s in ctx.spans
             if s[0] == "journal/snapshot" and "bytes" in s[3]]
    return sum(sizes) / len(sizes) / 1024.0 if sizes else None
