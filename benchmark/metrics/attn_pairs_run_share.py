"""Block pairs the attention layers' loops ran, of the block pairs their
bands hold by position alone (causal, inside the window), over the
window's dispatches and every layer kind (``trainer/dispatch``'s
``attn_pairs_run_<kind>`` over ``attn_pairs_in_band_<kind>``, which the
ledger sets from the step's own count): how far the segments of the
packed rows cut the band, 100 where they cut nothing.  A program that
counts no pairs has nothing to read."""


def read(run):
    from benchmark import run as bench
    from benchmark.reduce import stream_scopes

    pairs = [
        got for kind in bench.load_module("metrics", "attn_core_roofline").KINDS
        for got in stream_scopes.window_dispatches(run, f"attn_pairs_run_{kind}", f"attn_pairs_in_band_{kind}")
    ]
    in_band = sum(b for _, b in pairs)
    return 100.0 * sum(r for r, _ in pairs) / in_band if in_band else None
