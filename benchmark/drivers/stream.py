"""The online path fed transfer streams: the online driver's session and
closed loop on completion as they are, with blocks of this driver's own
making in the place of ``traffic.records``' and the train dispatch's
compiled text kept for the readers that name device time by scope.

A block is one dispatch of records in arrival order.  One stream per
child: its length from a clipped lognormal, its records back to back, the
next child's after them; ``rows`` rows of ``batch_size / rows`` positions
a step, so a stream cut at a row's end goes on in the next row as a new
segment.  Parents are drawn Zipf over the hosts ranked with the seed peers
first; targets are ``log1p`` of the cluster's bandwidth from parent to
child.  ``run.py`` has already called ``traffic.make_inputs`` for the
cluster and the probe graph; the blocks it made are not used.

Cell parameters: ``driver_params`` as the online driver's, and ``rows``;
``traffic``: ``stream_pool_blocks``, ``parent_zipf_s``; the stream-length
law is the configuration's (``model.stream_length``).  The positions of a
row are ``batch_size / rows``, written into ``config["model"]`` for the
program, the reference and ``step_flops`` alike.
"""

from __future__ import annotations

import numpy as np

from . import online

Window = online.Window


def stream_blocks(cluster, count: int, records: int, law: dict, zipf_s: float, seed: int):
    """``count`` blocks of ``records`` (src, dst, y), one seed one pool."""
    r = np.random.default_rng([online._seed32(seed), 5])
    need = count * records
    lengths = np.zeros(0, np.int64)
    while lengths.sum() < need:
        more = r.lognormal(np.log(law["median"]), law["sigma"], 1 + need // law["min"] // 16)
        lengths = np.concatenate([lengths, np.clip(np.rint(more), law["min"], law["max"]).astype(np.int64)])
    lengths = lengths[: int(np.searchsorted(np.cumsum(lengths), need)) + 1]
    # One stream per child, neighbours never the same child.
    turns = -(-len(lengths) // cluster.n)
    children = np.concatenate([r.permutation(cluster.n) for _ in range(turns)])[: len(lengths)]
    dst = np.repeat(children, lengths)[:need].astype(np.int32)
    ranked = np.argsort(-cluster.host_type, kind="stable")          # seed peers first
    p = 1.0 / np.arange(1, cluster.n + 1, dtype=np.float64) ** zipf_s
    rank = r.choice(cluster.n, size=need, p=p / p.sum())
    rank = np.where(ranked[rank] == dst, (rank + 1) % cluster.n, rank)   # no self transfer
    src = ranked[rank].astype(np.int32)
    y = np.log1p(cluster.bandwidth(src, dst, r)).astype(np.float32)
    cut = lambda a: [a[i * records:(i + 1) * records] for i in range(count)]
    return list(zip(cut(src), cut(dst), cut(y)))


def _prepare(ctx) -> None:
    """The cell's own blocks and the row's positions, once per context."""
    if getattr(ctx.inputs, "streams", False):
        return
    p, model = ctx.cell["driver_params"], ctx.config["model"]
    batch, rows = int(p["batch_size"]), int(p["rows"])
    if batch % rows:
        raise ValueError(f"batch_size {batch} is not {rows} whole rows")
    model["positions"] = batch // rows
    traffic = ctx.cell["traffic"]
    ctx.inputs.blocks = stream_blocks(
        ctx.inputs.cluster, int(traffic["stream_pool_blocks"]),
        int(p["super_steps"]) * batch, model["stream_length"],
        float(traffic["parent_zipf_s"]), ctx.seed,
    )
    ctx.inputs.streams = True


def reference_inputs(ctx) -> dict:
    _prepare(ctx)
    return online.reference_inputs(ctx)


class Session(online.Session):
    def __init__(self, ctx) -> None:
        _prepare(ctx)
        super().__init__(ctx)
        # From the persistent cache where the warm-up left it; set-up time.
        self.program_text = self.trainer.dispatch_program_text()

    def run_window(self, seconds: float) -> Window:
        window = super().run_window(seconds)
        window.extras["program_text"] = self.program_text
        return window

    def release(self) -> None:
        """The state goes and the dispatch's loaded program with it: the
        runtime keeps a loaded program's temporaries reserved (5.9 GB
        here), and the reference needs the room."""
        import jax

        super().release()
        jax.clear_caches()


def setup(ctx) -> Session:
    return Session(ctx)
