"""The expert layers (all five ``stream/moe/*`` scopes: router, dispatch,
routed experts, shared expert, combine) as a share of the device's busy
time."""


def read(run):
    from benchmark.reduce import stream_scopes

    return stream_scopes.share(run, stream_scopes.MOE)
