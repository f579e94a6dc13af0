"""The gated attention layer's blockwise softmax attention (scope
``stream/attn/core``, projections left out) as a share of the device's
busy time."""


def read(run):
    from benchmark.reduce import stream_scopes

    return stream_scopes.share(run, ["attn/core"])
