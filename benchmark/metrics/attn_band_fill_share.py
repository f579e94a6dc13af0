"""Keys the attention layers' queries attended (in the query's segment,
causal, inside its window) of the keys their bands hold by position alone
(causal, inside the window), over the window's dispatches and every layer
kind (``trainer/dispatch``'s ``attn_keys_attended_<kind>`` over
``attn_keys_in_band_<kind>``, which the ledger sets from the step's own
count): what share of a band run by position the segments leave
unmasked.  A program that counts no keys has nothing to read."""


def read(run):
    from benchmark import run as bench

    keys = bench.load_module("metrics", "attn_core_roofline").keys
    in_band = keys(run, "in_band")
    return 100.0 * keys(run, "attended") / in_band if in_band else None
