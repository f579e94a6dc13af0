"""Graph-partitioned neighbor aggregation with shard_map collectives.

SURVEY §2.6 / §5.7: the framework's analog of sequence/context parallelism
is partitioning the peer graph's neighbor aggregation across devices.  The
node table shards over the mesh's ``data`` axis; each device owns a
contiguous node block (its rows of the padded neighbor table) but its
nodes' neighbors live anywhere, so each aggregation layer performs one
**boundary exchange** — an all-gather of the node features over ICI (XLA
lowers it as a ring of ppermute hops, the same traffic pattern as ring
attention's K/V rotation) — followed by purely local gather + masked mean.

Cost model (scaling-book style): per layer, all-gather moves N·D·(n-1)/n
floats over ICI while the local gather+reduce does N/n·K·D FLOPs per
device — compute and collective overlap when XLA pipelines the layer, and
the exchange is the *only* cross-device traffic (indices/masks never move).

For graphs whose node features don't fit a chip even sharded, the next
step (round 2+) swaps the full all-gather for a halo exchange of just the
boundary node set per shard.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.gnn import NeighborTable
from .mesh import DATA_AXIS


def _local_aggregate(h_full: jax.Array, indices, mask, edge_feats) -> jax.Array:
    """Local block of the masked-mean aggregation against the gathered table."""
    nbr = jnp.take(h_full, indices, axis=0)                   # [N/n, K, D]
    nbr = jnp.concatenate([nbr, edge_feats.astype(nbr.dtype)], axis=-1)
    m = mask.astype(nbr.dtype)[..., None]
    denom = jnp.maximum(m.sum(axis=1), 1.0)
    return (nbr * m).sum(axis=1) / denom                      # [N/n, D+E]


def sharded_neighbor_aggregate(
    mesh: Mesh,
    h: jax.Array,
    table: NeighborTable,
    *,
    axis: str = DATA_AXIS,
) -> jax.Array:
    """Node-sharded masked-mean aggregation: h and table sharded on dim 0.

    h: [N, D] sharded P(axis); table rows sharded the same way (indices are
    GLOBAL node ids).  Returns [N, D+E] with the same sharding.
    """

    def body(h_block, indices, mask, edge_feats):
        # Boundary exchange: assemble the full node table locally (ring
        # all-gather over ICI); everything after is device-local.
        h_full = jax.lax.all_gather(h_block, axis, axis=0, tiled=True)
        return _local_aggregate(h_full, indices, mask, edge_feats)

    sharded = P(axis)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(sharded, sharded, sharded, sharded),
        out_specs=sharded,
    )(h, table.indices, table.mask, table.edge_feats)


def make_sharded_table(mesh: Mesh, table: NeighborTable, *, axis: str = DATA_AXIS) -> NeighborTable:
    """Place a host-built table with its node dim sharded over the mesh."""
    shard = NamedSharding(mesh, P(axis))
    return NeighborTable(
        indices=jax.device_put(table.indices, shard),
        mask=jax.device_put(table.mask, shard),
        edge_feats=jax.device_put(table.edge_feats, shard),
    )


def pad_nodes_for_mesh(n_nodes: int, mesh: Mesh, *, axis: str = DATA_AXIS) -> int:
    """Node count rounded up so every shard is equal (static shapes)."""
    n = mesh.shape[axis]
    return ((n_nodes + n - 1) // n) * n


# ---------------------------------------------------------------------------
# Halo exchange: ship only the boundary rows, not the whole table
# ---------------------------------------------------------------------------


class HaloPlan:
    """Host-side exchange plan for one graph snapshot.

    The full all-gather moves N·D floats to every device per layer; with a
    locality-partitioned graph each shard's neighbors mostly live on-shard,
    so only the **halo** — the off-shard rows its table references — needs
    to move.  The plan is static-shape (max-halo padded) so XLA compiles
    once; rebuild it when the graph snapshot changes, not per step.

    - send_idx   [n, n, H]  — for src device i: local rows to ship to each
                              dest j (row i used inside shard i).
    - local_idx  [N, K]     — the table's global indices remapped into each
                              shard's local space: [0,S) own rows, then
                              halo slots [S + j·H + p].
    - halo       H          — max off-shard rows needed from any one shard.
    """

    def __init__(
        self, n_shards: int, shard_size: int, send_idx, local_idx, halo: int,
        table_digest: str = "",
    ):
        self.n_shards = n_shards
        self.shard_size = shard_size
        self.send_idx = send_idx
        self.local_idx = local_idx
        self.halo = halo
        # Fingerprint of the table's indices at plan time: the plan remaps
        # THOSE indices, so pairing it with a resampled table would
        # silently misalign features.
        self.table_digest = table_digest


def _table_digest(table: NeighborTable) -> str:
    import hashlib
    import numpy as np

    return hashlib.sha1(np.asarray(table.indices).tobytes()).hexdigest()[:16]


def _check_plan(plan: "HaloPlan", table: NeighborTable) -> None:
    """Refuse a plan built for a different table sampling.  Under jit the
    indices are tracers (no concrete bytes to hash) — the caller owns
    plan/table pairing there; the eager path stays guarded."""
    if not plan.table_digest or isinstance(table.indices, jax.core.Tracer):
        return
    if plan.table_digest != _table_digest(table):
        raise ValueError(
            "HaloPlan was built for a different table sampling — rebuild "
            "the plan whenever build_neighbor_table resamples (per epoch)"
        )


def build_halo_plan(table: NeighborTable, mesh: Mesh, *, axis: str = DATA_AXIS) -> HaloPlan:
    import numpy as np

    n = mesh.shape[axis]
    indices = np.asarray(table.indices)
    N, K = indices.shape
    if N % n:
        raise ValueError(f"node count {N} not divisible by {n} shards")
    S = N // n

    # needed[j][i]: sorted unique global rows shard j needs from shard i.
    # uniq is sorted, so each source shard's rows are one contiguous
    # searchsorted slice — no per-element Python (O(N·K) total, numpy).
    needed = [[None] * n for _ in range(n)]
    halo = 0
    bounds = np.arange(n + 1, dtype=np.int64) * S
    for j in range(n):
        block = indices[j * S : (j + 1) * S]
        uniq = np.unique(block)
        cuts = np.searchsorted(uniq, bounds)
        for i in range(n):
            rows = uniq[cuts[i] : cuts[i + 1]]
            if i == j:
                rows = rows[:0]  # own rows need no exchange
            needed[j][i] = rows
            halo = max(halo, len(rows))
    halo = max(halo, 1)

    # send_idx[i][j]: local offsets shard i ships to shard j (pad with 0).
    send_idx = np.zeros((n, n, halo), dtype=np.int32)
    # slot[g] = shard j's local slot for global id g; only ids that occur
    # in shard j's block are ever read, so stale entries are harmless.
    local_idx = np.empty_like(indices, dtype=np.int32)
    slot = np.empty(N, dtype=np.int32)
    for j in range(n):
        slot[j * S : (j + 1) * S] = np.arange(S, dtype=np.int32)
        for i in range(n):
            rows = needed[j][i]
            send_idx[i, j, : len(rows)] = rows - i * S
            slot[rows] = S + i * halo + np.arange(len(rows), dtype=np.int32)
        local_idx[j * S : (j + 1) * S] = slot[indices[j * S : (j + 1) * S]]
    return HaloPlan(
        n, S, jnp.asarray(send_idx), jnp.asarray(local_idx), halo,
        table_digest=_table_digest(table),
    )


def _halo_assemble(h_block, my_send_idx, axis: str) -> jax.Array:
    """Inside a shard_map body: exchange boundary rows and return the
    shard's LOCAL node table ``[S + n·H, D]`` (own rows first, then halo
    slots laid out as ``S + src_shard·H + p`` — the order
    ``build_halo_plan`` remapped ``local_idx`` against)."""
    send = jnp.take(h_block, my_send_idx[0], axis=0)        # [n, H, D]
    recv = jax.lax.all_to_all(
        send, axis, split_axis=0, concat_axis=0, tiled=False
    )
    # recv [n, H, D]: slice i = rows shipped by shard i to this shard.
    return jnp.concatenate(
        [h_block, recv.reshape(-1, h_block.shape[-1])], axis=0
    )


@partial(jax.jit, static_argnames=("mesh", "hops", "axis"))
def _sharded_precompute_impl(
    node_feats, mask, edge_feats, send_idx, local_idx, *, mesh, hops, axis
):
    from ..models.hop import _hop_parts

    def body(x_block, my_send_idx, li, m, ef):
        # Per hop the aggregate keeps D, so ONE plan serves every hop's
        # exchange; the math itself is models.hop._hop_parts — shared
        # with the replicated oracle so the two cannot drift.
        return _hop_parts(
            x_block.astype(jnp.float32),
            m,
            ef,
            lambda h: jnp.take(_halo_assemble(h, my_send_idx, axis), li, axis=0),
            hops,
        )

    sharded = P(axis)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(sharded, sharded, sharded, sharded, sharded),
        out_specs=sharded,
    )(node_feats, send_idx, local_idx, mask, edge_feats)


def precompute_hop_features_sharded(
    mesh: Mesh,
    node_feats: jax.Array,
    table: NeighborTable,
    plan: HaloPlan,
    *,
    hops: int = 2,
    axis: str = DATA_AXIS,
) -> jax.Array:
    """Node-sharded ``models.hop.precompute_hop_features``.

    The replicated precompute holds the FULL [N, F] feature table (and a
    [N, K, D] gather) on every chip — at config[4]'s multi-M-node scale
    that table, not the model, is the memory wall.  Here every chip owns
    S = N/n node rows; per hop the only cross-chip traffic is the halo
    all-to-all of [n·H, D] boundary rows (H = max off-shard rows any
    shard references), after which the gather + both masked means are
    device-local.  Per-chip working set drops from N·D to (S + n·H)·D
    and the output stays sharded P(axis) — it feeds straight into
    ``node_sharding="model"`` training without a host round-trip.

    Jits internally (one fused program; cached on mesh/hops/axis) so
    eager callers get the same footprint the bench measures.  Numerically
    identical to the replicated oracle — the hop math IS the oracle's
    (models.hop._hop_parts); verified in dryrun_multichip and
    tests/test_ops.py.
    """
    _check_plan(plan, table)
    return _sharded_precompute_impl(
        node_feats,
        table.mask,
        table.edge_feats,
        plan.send_idx,
        plan.local_idx,
        mesh=mesh,
        hops=hops,
        axis=axis,
    )


def halo_neighbor_aggregate(
    mesh: Mesh,
    h: jax.Array,
    table: NeighborTable,
    plan: HaloPlan,
    *,
    axis: str = DATA_AXIS,
) -> jax.Array:
    """Masked-mean aggregation with boundary-only exchange.

    Per layer, one all-to-all of [n·H, D] rows replaces the [N, D]
    all-gather — with a locality-aware partition H ≪ S and the collective
    traffic drops by ~S/H.  Numerically identical to the full exchange.
    """
    _check_plan(plan, table)

    def body(h_block, my_send_idx, local_idx, mask, edge_feats):
        # h_block [S, D]; my_send_idx [1, n, H] (this device's row of the
        # plan); exchange boundary rows, then gather locally.
        local = _halo_assemble(h_block, my_send_idx, axis)       # [S + n·H, D]
        nbr = jnp.take(local, local_idx, axis=0)                 # [S, K, D]
        nbr = jnp.concatenate([nbr, edge_feats.astype(nbr.dtype)], axis=-1)
        m = mask.astype(nbr.dtype)[..., None]
        denom = jnp.maximum(m.sum(axis=1), 1.0)
        return (nbr * m).sum(axis=1) / denom

    sharded = P(axis)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(sharded, sharded, sharded, sharded, sharded),
        out_specs=sharded,
    )(
        h,
        plan.send_idx,            # dim 0 (src device) sharded
        plan.local_idx,
        table.mask,
        table.edge_feats,
    )
