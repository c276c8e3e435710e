"""Wall seconds of the host partition build (``partition_2d_streaming``)
that filled this checkout's graph cache, stored with the cache."""


def read(ctx):
    return ctx.graph.build_s
