"""Residual cache (``serve/residual_cache``): hits over lookups of the
window's server (``ResidualCache.stats``)."""


def read(ctx):
    st = ctx.server.cache.stats
    lookups = st.hits + st.misses
    return st.hits / lookups if lookups else None
