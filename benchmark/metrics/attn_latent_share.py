"""The latent attention's low-rank products, their two norms and the
shared key's RoPE and broadcast (scope ``stream/attn/latent``) as a share
of the device's busy time.  A program with no such scope has nothing to
read."""


def read(run):
    from benchmark.reduce import stream_scopes

    got = stream_scopes.seconds(run)
    if got is None or not got.get("attn/latent"):
        return None
    return stream_scopes.share(run, ["attn/latent"])
