"""Batcher (``serve/batcher``): live rows over padded rows of every launch
the window's server made (``ServerStats``)."""


def read(ctx):
    st = ctx.server.stats
    if not st.padded_rows:
        return None
    return st.batched_rows / st.padded_rows
