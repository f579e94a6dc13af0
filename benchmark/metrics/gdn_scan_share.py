"""The Gated DeltaNet layers' chunked delta-rule scans (scope
``stream/gdn/scan``: the chunk's triangular system, the loop over chunks,
forward, recomputed and backward) as a share of the device's busy time."""


def read(run):
    from benchmark.reduce import stream_scopes

    return stream_scopes.share(run, ["gdn/scan"])
