"""Online graph trainer: continuous two-stream ingest + mid-training
snapshot refresh (BASELINE configs[5] as written).

The reference's Train stream feeds BOTH record types continuously —
download rows and network-topology rows (trainer/service/service_v1.go:
128-143 demuxes TrainMlpRequest / TrainGnnRequest on one stream).  Its
training consumer was a stub; here the consumer is the flagship hop
ranker running ONLINE:

- **downloads stream** → fixed-shape edge dispatches ([super_steps,
  batch] src/dst/target), one jitted ``lax.scan`` per dispatch;
- **topology stream** → a bounded most-recent window of probe edges;
  every ``refresh_every`` dispatches the window becomes a NEW graph
  snapshot: ``build_neighbor_table`` + ``precompute_hop_features`` re-run
  mid-training and the hop tables hot-swap **without touching the
  optimizer** (params, Adam moments, LR schedule position, dropout
  stream all continue — the learnable node embedding persists across
  snapshots because node identity does);
- the swap does not recompile: hop features and table are *arguments*
  of the jitted dispatch, and every snapshot has the same static shape
  ([num_nodes, F] / [num_nodes, K]).

Checkpoint/resume (orbax): params, opt state, step, dispatch, snapshot
index, records seen, PLUS the current topology window and node features
— the graph snapshot itself is derived state, rebuilt (deterministically:
build_neighbor_table seeds its sampler) at restore, so a resume lands on
the identical hop tables even when the kill fell between two refreshes.
Byte-identity across a refresh boundary is asserted in
tests/test_online_graph.py and proven at the 1B scale by
tools/soak_online_1b.py.
"""

from __future__ import annotations

import collections
import hashlib
import logging
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import build_ranker
from ..models.gnn import NeighborTable, build_neighbor_table
from ..models.hop import HopConfig
# Hoisted + static-hops so every snapshot build hits ONE traced program —
# the single cached wrapper shared with trainer/train.py (one DF010
# compile-budget site instead of one per importer).
from ..models.hop import precompute_hop_features_jit as _precompute_jit
from ..parallel.mesh import MODEL_AXIS
from ..utils.tracing import default_tracer
from . import program_scopes
from .metrics import (
    ONLINE_DISPATCHES_IN_FLIGHT,
    ONLINE_NODES_RECYCLED,
    ONLINE_RECORDS_ENQUEUED,
    ONLINE_RECORDS_TRAINED,
)
from .train import TrainConfig, TrainState, _graph_train_step, _make_optimizer

logger = logging.getLogger(__name__)


def state_hash(state) -> str:
    """sha256 over the params + optimizer bytes — THE byte-identity
    fingerprint the soak tools and tests compare (one definition, so
    'identical' always means the same thing)."""
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(
        {"params": state.params, "opt": state.opt_state}
    ):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


class WireIngestAdapter:
    """Routes the ``Train`` stream's DECODED rows into an
    ``OnlineGraphTrainer`` — the reference's continuous two-stream feed
    (service_v1.go:128-143) closed end to end over the real wire:
    ``TrainerService(online_sink=this)`` + ``StreamingRowDecoder``.

    Row endpoints arrive as HASH BUCKETS (records/features.py); the
    adapter assigns dense node ids on first sight (capped at the
    trainer's ``num_nodes`` — overflow edges are counted and dropped,
    with a WARNING on first overflow, never silently remapped), keeps
    per-node host-feature sums from the download payloads (the
    node-feature stream), and hands the trainer a LAZY feature source —
    the running mean is materialized once per snapshot build, not per
    wire chunk.

    **Node-id lifecycle** (``OnlineGraphConfig.node_ttl > 0``): real
    swarms churn, so a full table must not freeze the trainer on the
    early-arrivals subgraph.  Mirroring the scheduler's host TTL GC
    (reference scheduler/config/config.go:176-197), a host unseen on
    either stream for ``node_ttl`` seconds is evicted when capacity is
    needed: its dense id returns to a free pool, its feature
    accumulators reset, and the trainer queues an embedding +
    optimizer-moment row reset (applied on the training thread —
    ``OnlineGraphTrainer.apply_pending_recycles``).  Drops while the
    table is full and nothing has expired are TRANSIENT: the same host
    maps successfully once an eviction frees capacity.  Aliasing —
    topology-window or queued edges that still reference a recycled id
    describe the id's previous owner until they age out of the bounded
    window — matches the reference, where GC'd hosts vanish only at the
    next probe round.  Lifecycle mode is wall-clock-driven and therefore
    trades strict byte-identity replay for capacity recycling; the
    determinism soaks keep ``node_ttl=0`` (the default, which preserves
    the fixed first-come mapping exactly).

    **Native fast path** (``OnlineGraphConfig.native_ingest``, default
    on, silent fallback): this class is the SPEC; when the C++ engine
    is available the whole per-chunk pass — mapping, lifecycle,
    feature accumulation, edge buffering — runs in native.cpp's oi_*
    engine without the GIL, and the trainer takes dispatch blocks
    straight from the engine's edge ring (``trainer.block_source``)
    instead of the Python queue.  The measured ceiling of the composed
    wire-fed loop was the single Python consumer process compositing
    every stage under one GIL (BENCHMARKS.md bottleneck ledger), not
    any stage's algorithm.  One deliberate divergence: the native
    engine folds EVERY kept row into the feature means (no
    FEATURE_SAMPLE_ROWS sampling — C++ can afford the full pass).
    """

    def __init__(
        self, trainer: "OnlineGraphTrainer", *, use_native: bool = None
    ) -> None:
        from ..records.features import (
            DOWNLOAD_COLUMNS,
            HOST_FEATURE_DIM,
            NUM_HASH_BUCKETS,
        )

        self.trainer = trainer
        n = trainer.config.num_nodes
        self._native = None
        if use_native is None:
            use_native = trainer.config.native_ingest
        if use_native:
            try:
                from ..native import NativeOnlineIngest

                cfg = trainer.config
                ring = max(cfg.queue_capacity, 2) * (
                    cfg.super_steps * cfg.batch_size
                )
                self._native = NativeOnlineIngest(
                    n, NUM_HASH_BUCKETS, HOST_FEATURE_DIM,
                    len(DOWNLOAD_COLUMNS), cfg.node_ttl, ring,
                )
            except Exception as exc:  # noqa: BLE001 — optimization only
                logger.warning(
                    "native ingest unavailable (%s); python fallback", exc
                )
                self._native = None
            if self._native is not None:
                if (
                    not trainer._downloads.empty()
                    or trainer._leftover is not None
                ):
                    # Switching to the engine's edge ring would silently
                    # strand edges already in the Python queue.  (When
                    # the library is UNAVAILABLE the python fallback
                    # keeps them — so check only after construction.)
                    self._native.close()
                    self._native = None
                    raise RuntimeError(
                        "cannot attach a native-ingest adapter after "
                        "feed_downloads: queued edges would be lost "
                        "(attach the adapter first, or set "
                        "native_ingest=False)"
                    )
                trainer.block_source = self._native_block
        # Vectorized bucket → dense-id table (the ingest hot path must
        # sustain wire rate): -2 = unseen, -1 = overflow.  Unused (but
        # kept allocated) when the native engine owns the mapping.
        self._id_table = np.full(NUM_HASH_BUCKETS, -2, np.int32)
        self._next_id = 0
        self._feat_sum = np.zeros((n, HOST_FEATURE_DIM), np.float32)
        self._feat_cnt = np.zeros(n, np.float32)
        self._py_overflow = 0  # python-path edges + native-path topo drops
        self._py_evicted = 0
        self._native_overflow_seen = 0  # engine counter high-water (metrics)
        self._warned_full = False
        # Lifecycle state: last time each dense id was seen on any
        # stream, its current bucket (for reverse unmapping), and the
        # free pool of recycled ids.
        self._last_seen = np.zeros(n, np.float64)
        self._bucket_of = np.full(n, -1, np.int64)
        self._free: List[int] = []
        self._last_evict_scan = float("-inf")
        # EPOCH time, not monotonic: last-seen stamps live in the
        # checkpoint and must stay comparable across process restarts.
        self.clock = time.time  # injectable for deterministic tests
        self._mu = threading.Lock()
        trainer.node_feature_source = self.node_features
        trainer._adapter = self
        if trainer._adapter_restore is not None:
            self._apply_restore(trainer._adapter_restore)

    @property
    def engine(self) -> str:
        """Which ingest engine this adapter got: "native" (the C++ oi_*
        engine) or "python" (this class; also what ``native_ingest=True``
        falls back to when the library cannot be built)."""
        return "native" if self._native is not None else "python"

    @property
    def overflow_edges(self) -> int:
        if self._native is not None:
            return self._native.stats()["overflow_edges"] + self._py_overflow
        return self._py_overflow

    @property
    def evicted_nodes(self) -> int:
        if self._native is not None:
            return self._native.stats()["evicted_nodes"]
        return self._py_evicted

    def _native_block(self, timeout: float):
        """trainer.block_source: one [super_steps, batch] dispatch block
        straight out of the engine's edge ring (a single C++ memcpy —
        no Python-level queue/concatenate on the hot path)."""
        cfg = self.trainer.config
        need = cfg.super_steps * cfg.batch_size
        got = self._native.take_edges(need, timeout)
        if got is None:
            return None
        shape = (cfg.super_steps, cfg.batch_size)
        return (
            got[0].reshape(shape), got[1].reshape(shape),
            got[2].reshape(shape),
        )

    def poll_recycled(self) -> None:
        """Drain engine-side evictions into the trainer's recycle queue
        (the python path queues them inline in _evict_expired)."""
        if self._native is None:
            return
        from .metrics import ONLINE_NODES_EVICTED

        while True:
            ids = self._native.take_recycled()
            if not len(ids):
                return
            ONLINE_NODES_EVICTED.inc(len(ids))
            self.trainer.request_recycle(ids)

    def _apply_restore(self, st: dict) -> None:
        """Re-attach a checkpointed id mapping: the mapping is NOT
        derivable from the stream in ttl mode (eviction is clock-driven),
        so it rides in the trainer checkpoint — host X keeps the dense id
        whose embedding learned X.  The state format is shared between
        the python and native engines: either can restore the other's."""
        n = self.trainer.config.num_nodes
        if len(st["adapter_bucket_of"]) != n:
            # A mismatched num_nodes would OOB-read in the native import
            # (and silently desync the python arrays).
            raise ValueError(
                f"checkpoint adapter state is for num_nodes="
                f"{len(st['adapter_bucket_of'])}, trainer has {n}"
            )
        free = [int(i) for i in st["adapter_free"] if i >= 0]
        if self._native is not None:
            self._native.import_state(
                st["adapter_id_table"], st["adapter_bucket_of"],
                st["adapter_last_seen"], np.asarray(free, np.int32),
                st["adapter_feat_sum"], st["adapter_feat_cnt"],
                int(st["adapter_next_id"]),
                int(st["adapter_overflow_edges"]),
                int(st["adapter_evicted_nodes"]),
            )
            self._py_overflow = 0
            # Sync the metrics high-water to the imported counter, else
            # the first post-restore drop re-counts the whole history.
            self._native_overflow_seen = int(st["adapter_overflow_edges"])
            return
        with self._mu:
            self._id_table = np.asarray(st["adapter_id_table"], np.int32).copy()
            self._bucket_of = np.asarray(st["adapter_bucket_of"], np.int64).copy()
            self._last_seen = np.asarray(st["adapter_last_seen"], np.float64).copy()
            self._free = free
            self._next_id = int(st["adapter_next_id"])
            self._feat_sum = np.asarray(st["adapter_feat_sum"], np.float32).copy()
            self._feat_cnt = np.asarray(st["adapter_feat_cnt"], np.float32).copy()
            self._py_overflow = int(st["adapter_overflow_edges"])
            self._py_evicted = int(st["adapter_evicted_nodes"])
            self._last_evict_scan = float("-inf")

    def snapshot_for_checkpoint(self) -> dict:
        """A consistent (mapping, applied-row-resets) pair for the
        trainer checkpoint: drains + applies pending recycles, then
        snapshots the mapping, retrying if an eviction raced in between
        — a saved mapping must never outrun its embedding resets."""
        while True:
            self.poll_recycled()
            self.trainer.apply_pending_recycles()
            if self._native is not None:
                st = self._native.export_state()
                if st is None:  # eviction landed after the drain
                    continue
                return {
                    "adapter_id_table": st["id_table"],
                    "adapter_bucket_of": st["bucket_of"],
                    "adapter_last_seen": st["last_seen"],
                    # Trailing -1 sentinel: orbax rejects zero-size
                    # arrays, and free ids are always >= 0.
                    "adapter_free": np.concatenate(
                        [st["free"].astype(np.int64), [-1]]
                    ),
                    "adapter_next_id": st["next_id"],
                    "adapter_feat_sum": st["feat_sum"],
                    "adapter_feat_cnt": st["feat_cnt"],
                    "adapter_overflow_edges": (
                        st["overflow_edges"] + self._py_overflow
                    ),
                    "adapter_evicted_nodes": st["evicted_nodes"],
                }
            with self._mu:
                with self.trainer._recycle_lock:
                    if self.trainer._pending_recycle:
                        continue
                return {
                    "adapter_id_table": self._id_table.copy(),
                    "adapter_bucket_of": self._bucket_of.copy(),
                    "adapter_last_seen": self._last_seen.copy(),
                    "adapter_free": np.concatenate(
                        [np.asarray(self._free, np.int64), [-1]]
                    ),
                    "adapter_next_id": int(self._next_id),
                    "adapter_feat_sum": self._feat_sum.copy(),
                    "adapter_feat_cnt": self._feat_cnt.copy(),
                    "adapter_overflow_edges": int(self._py_overflow),
                    "adapter_evicted_nodes": int(self._py_evicted),
                }

    def _evict_expired(self, now: float) -> int:
        """Reclaim dense ids whose hosts fell silent for ``node_ttl``
        (the scheduler's host GC semantics).  Called under ``_mu`` from
        the mapping slow path when the table is full; the O(num_nodes)
        scan is throttled to once per ttl/4."""
        ttl = float(self.trainer.config.node_ttl)
        if ttl <= 0 or now - self._last_evict_scan < ttl * 0.25:
            return 0
        self._last_evict_scan = now
        active = self._bucket_of >= 0
        expired = np.nonzero(active & (now - self._last_seen > ttl))[0]
        if len(expired) == 0:
            return 0
        self._id_table[self._bucket_of[expired]] = -2
        self._bucket_of[expired] = -1
        self._feat_sum[expired] = 0.0
        self._feat_cnt[expired] = 0.0
        self._free.extend(int(i) for i in expired)
        self._py_evicted += len(expired)
        # Un-memoize overflow buckets: freed capacity means previously
        # dropped hosts may claim ids on their next appearance.
        self._id_table[self._id_table == -1] = -2
        self.trainer.request_recycle(expired)
        from .metrics import ONLINE_NODES_EVICTED

        ONLINE_NODES_EVICTED.inc(len(expired))
        logger.info(
            "node lifecycle: evicted %d expired hosts (ttl=%.0fs), "
            "%d ids free", len(expired), ttl, len(self._free),
        )
        return len(expired)

    def _map_ids(self, buckets: np.ndarray, now: float) -> np.ndarray:
        """bucket → dense id; -1 for overflow (node table full).  One
        vectorized gather in steady state; Python only touches buckets
        never seen before (or, in ttl mode, previously dropped)."""
        b = buckets.astype(np.int64)
        out = self._id_table[b]
        ttl_mode = self.trainer.config.node_ttl > 0
        if ttl_mode:
            # Touch BEFORE any eviction: a host present in this very
            # chunk is alive by definition and must not be reclaimed by
            # the scan below, however long it was silent before.
            seen = out[out >= 0]
            if len(seen):
                self._last_seen[seen] = now
        # ttl mode also retries -1 (dropped) buckets: expired capacity
        # may have freed up since — drops must stay transient even when
        # no brand-new bucket arrives to trigger the slow path.
        if (out == -2).any() or (ttl_mode and (out == -1).any()):
            cap = self.trainer.config.num_nodes
            if not self._free and self._next_id >= cap:
                if self._evict_expired(now):
                    # Eviction un-memoized -1 buckets; re-gather so this
                    # chunk's dropped hosts remap right now.
                    out = self._id_table[b]
            for nb in np.unique(b[out == -2]):
                if self._id_table[nb] != -2:
                    continue
                if not self._free and self._next_id >= cap:
                    # The pre-loop attempt only fires when the pool was
                    # ALREADY empty; a small leftover pool can drain
                    # mid-chunk with expired ids still reclaimable (the
                    # scan throttle keeps repeat calls cheap).
                    self._evict_expired(now)
                if self._free:
                    nid = self._free.pop()
                elif self._next_id < cap:
                    nid = self._next_id
                    self._next_id += 1
                else:
                    self._id_table[nb] = -1
                    continue
                self._id_table[nb] = nid
                self._bucket_of[nid] = nb
                self._last_seen[nid] = now
            out = self._id_table[b]
        return out

    def _warn_table_full_once(self) -> None:
        """One warning per adapter lifetime, whichever path drops first
        (callers hold _mu)."""
        if self._warned_full:
            return
        self._warned_full = True
        logger.warning(
            "node table full (num_nodes=%d): dropping edges touching "
            "unmapped hosts%s", self.trainer.config.num_nodes,
            "" if self.trainer.config.node_ttl > 0
            else " (node_ttl=0: drops are permanent)",
        )

    def _count_overflow(self, n_dropped: int) -> None:
        if n_dropped <= 0:
            return
        self._warn_table_full_once()
        self._py_overflow += n_dropped
        from .metrics import ONLINE_OVERFLOW_EDGES

        ONLINE_OVERFLOW_EDGES.inc(n_dropped)

    def close(self) -> None:
        """Release the native engine (its buffers are invisible to the
        Python gc; a parked wire feeder also keeps it alive).  Final
        counters fold into the python-side fields so overflow_edges /
        evicted_nodes stay readable after close.  Idempotent."""
        if self._native is None:
            return
        st = self._native.stats()
        self._py_overflow += int(st["overflow_edges"])
        self._py_evicted = int(st["evicted_nodes"])
        self._native_overflow_seen = 0
        self._native.close()
        self._native = None
        self.trainer.block_source = None

    def node_features(self) -> np.ndarray:
        """Materialize the running per-node feature means — called by the
        trainer ONCE per snapshot build (lazy; never per chunk)."""
        if self._native is not None:
            return self._native.node_features()
        with self._mu:
            return self._feat_sum / np.maximum(self._feat_cnt[:, None], 1.0)

    # Feature-mean accumulation samples at most this many rows per feed:
    # the means converge long before every row has voted, and the full
    # per-row bincount pass was a measured chunk of the wire-ingest
    # budget.  Edges (the training signal) are NEVER sampled.
    FEATURE_SAMPLE_ROWS = 262_144

    def feed_download_rows(self, rows: np.ndarray) -> None:
        if rows.size == 0:
            return
        now = self.clock()
        if self._native is not None:
            # The whole per-chunk pass (map, lifecycle, accumulate,
            # ring append w/ backpressure) is ONE GIL-free call.
            self._native.feed_download_rows(rows, now)
            # Engine-side drops must stay observable: same warning +
            # metric the python path emits, driven by the counter delta
            # (under _mu — wire threads feed concurrently).
            with self._mu:
                ov = self._native.stats()["overflow_edges"]
                dropped = ov - self._native_overflow_seen
                if dropped > 0:
                    self._native_overflow_seen = ov
                    self._warn_table_full_once()
                    from .metrics import ONLINE_OVERFLOW_EDGES

                    ONLINE_OVERFLOW_EDGES.inc(dropped)
            return
        with self._mu:
            # ONE mapping call over both endpoint columns: every host in
            # the chunk is touched before any eviction runs, so a live
            # dst can never be reclaimed by the src column's slow path.
            both = self._map_ids(
                np.concatenate([rows[:, 0], rows[:, 1]]), now
            )
            src, dst = both[: len(rows)], both[len(rows):]
            ok = (src >= 0) & (dst >= 0)
            n_bad = int(len(ok) - np.count_nonzero(ok))
            self._count_overflow(n_bad)
            if n_bad:
                src, dst = src[ok], dst[ok]
                kept = rows[ok]
            else:
                kept = rows  # fast path: no 100MB boolean-mask copy
            # Node-feature stream: ONE shared accumulator with the batch
            # trainer (records.features.accumulate_host_feature_sums) so
            # the parent/child attribution cannot drift between paths.
            from ..records.features import accumulate_host_feature_sums

            m = min(len(kept), self.FEATURE_SAMPLE_ROWS)
            accumulate_host_feature_sums(
                kept[:m], src[:m], dst[:m], self._feat_sum, self._feat_cnt
            )
        if len(src):
            self.trainer.feed_downloads(
                src, dst, kept[:, -1].astype(np.float32)
            )

    def feed_topology_rows(self, rows: np.ndarray) -> None:
        if rows.size == 0:
            return
        now = self.clock()
        with self._mu:
            # Only the mapping call differs between engines; the engine
            # has its own mutex, so holding _mu around it just keeps the
            # counter updates below single-writer like the python path.
            flat = np.concatenate([rows[:, 0], rows[:, 1]])
            if self._native is not None:
                both = self._native.map_buckets(flat, now)
            else:
                both = self._map_ids(flat, now)
            src, dst = both[: len(rows)], both[len(rows):]
            ok = (src >= 0) & (dst >= 0)
            self._count_overflow(int((~ok).sum()))
            src, dst = src[ok], dst[ok]
            rtt = rows[ok, 2].astype(np.float32)
        if len(src):
            self.trainer.feed_topology(src, dst, rtt)


@dataclass
class OnlineGraphConfig:
    num_nodes: int
    max_neighbors: int = 16
    batch_size: int = 131_072
    super_steps: int = 64            # train steps per jitted dispatch
    refresh_every: int = 0           # dispatches between snapshot swaps (0 = static)
    topo_window: int = 1_000_000     # most-recent probe edges kept for the next snapshot
    checkpoint_every: int = 0        # dispatches (0 = off)
    # Node-id lifecycle for the wire adapter: hosts unseen for this many
    # seconds are evicted when the table is full and their dense ids
    # recycled (embedding + moment rows reset).  0 = off: the mapping is
    # frozen first-come and overflow drops are permanent (the strictly
    # deterministic mode the byte-identity soaks use).
    node_ttl: float = 0.0
    queue_capacity: int = 2          # dispatch blocks of ingest backpressure
    # A HopConfig or a StreamRankerConfig: the trainer builds the ranker
    # from the configuration's type (models.build_ranker).
    model: object = field(default_factory=HopConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    total_steps_hint: int = 100_000  # LR schedule horizon
    # C++ wire-ingest fast path (native.cpp oi_* engine): mapping,
    # lifecycle, feature accumulation and edge buffering run GIL-free,
    # and the trainer takes dispatch blocks straight from the engine's
    # ring.  Silently falls back to the (spec) Python adapter when the
    # native library can't build.
    native_ingest: bool = True
    # The config[4]×[5] mode: a (data, model) Mesh with
    # node_sharding="model" partitions the hop table, the embedding
    # table (+ its optimizer moments) AND the snapshot precompute by
    # node over the model axis — the online trainer at the scale where
    # node tables exceed one chip's HBM.  None = single-device.
    mesh: object = None
    node_sharding: str = "replicated"


class OnlineGraphTrainer:
    """The configs[5] consumer: see module docstring."""

    def __init__(
        self,
        config: OnlineGraphConfig,
        *,
        node_feats: np.ndarray,
        topo_src: np.ndarray,
        topo_dst: np.ndarray,
        topo_rtt: np.ndarray,
        checkpoint_dir: Optional[str] = None,
    ) -> None:
        """``node_feats`` + the initial probe edges bootstrap snapshot 0 —
        an online trainer still needs one graph to start ranking on."""
        self.config = config
        self.checkpoint_dir = checkpoint_dir
        ranker = build_ranker(config.model)
        self.model = ranker.module
        # (dst, y) -> query edge features inside the step, or None; and
        # what the ranker makes of the ``aux`` a finished dispatch counted.
        self._query_feats = ranker.query_feats
        self._fold_aux = ranker.fold
        self._run_attrs = ranker.run_attrs
        if config.batch_size % ranker.batch_multiple:
            raise ValueError(
                f"batch_size {config.batch_size} is not a multiple of the "
                f"{ranker.batch_multiple} records this ranker reads as one row"
            )
        if config.node_ttl > 0 and not ranker.servable:
            from ..models import require_servable

            require_servable(config.model, "OnlineGraphConfig(node_ttl > 0)")

        self._topo_lock = threading.Lock()
        self._topo_parts: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self._topo_count = 0
        self._fed_since_swap = 0
        self.node_feats = np.asarray(node_feats, np.float32)
        # Optional lazy provider (the wire adapter sets it): consulted at
        # each snapshot build INSTEAD of the last set_node_features value,
        # so per-chunk feeds never materialize the full feature matrix.
        self.node_feature_source = None
        self.feed_topology(topo_src, topo_dst, topo_rtt)

        self._downloads: "queue.Queue" = queue.Queue(maxsize=config.queue_capacity)
        self._leftover: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None
        # Set by a native-ingest adapter: dispatch blocks come straight
        # from the C++ edge ring instead of the Python queue.
        self.block_source = None

        self.dispatch = 0
        self.snapshot_idx = 0
        self.records_seen = 0
        # Mean training loss of the newest dispatch (a device scalar:
        # reading it waits for that dispatch).
        self.last_loss: Optional[jax.Array] = None
        # The ledger of what the device has finished.  ``dispatch`` and
        # ``records_seen`` count what the host has ENQUEUED; a dispatch is
        # asynchronous, so the host may be many of them ahead of the chip.
        # (dispatch index, loss, rows, aux, span) of every dispatch enqueued
        # and not yet seen finished; ``rows`` is what the steps of that
        # dispatch counted on the device (TrainState.rows after less
        # before), ``aux`` what the model counted (TrainState.aux, likewise).
        self._in_flight: collections.deque = collections.deque()
        self.records_trained = 0        # exact, from the device
        self.dispatches_completed = 0   # in ``dispatch``'s numbering
        self.dispatches_in_flight_max = 0
        # Recycled ids queued by the (ingest-thread) wire adapter; the
        # row resets run on the TRAINING thread between dispatches —
        # the state may be donated mid-dispatch when the adapter fires.
        self._recycle_lock = threading.Lock()
        self._pending_recycle: List[np.ndarray] = []
        self.nodes_recycled = 0
        # Attached wire adapter (if any) — its id mapping checkpoints
        # with the trainer; resume() stashes the restored copy here for
        # the next make_wire_adapter() to re-attach.
        self._adapter: Optional["WireIngestAdapter"] = None
        self._adapter_restore: Optional[dict] = None
        self._window: Tuple[np.ndarray, np.ndarray, np.ndarray] = self._drain_window()
        self._fed_since_swap = 0  # bootstrap topology = snapshot 0's input
        # Snapshot 0 builds LAZILY (_ensure_snapshot) — a resume() right
        # after the constructor replaces the window anyway, and the build
        # is seconds at 100k nodes.
        self.table: Optional[NeighborTable] = None
        self.hop_feats: Optional[jax.Array] = None

        # -- model / optimizer (created ONCE; survives every swap) ----------
        # Params depend on SHAPES only — dummy zero tables keep the
        # constructor free of the snapshot build.
        d_in = self.node_feats.shape[1]
        hop_dim = d_in * (1 + 2 * config.model.hops) + 2  # _hop_parts layout
        dummy_feats = jnp.zeros((config.num_nodes, hop_dim), jnp.float32)
        dummy_table = NeighborTable(
            indices=jnp.zeros((config.num_nodes, config.max_neighbors), jnp.int32),
            mask=jnp.zeros((config.num_nodes, config.max_neighbors), jnp.float32),
            edge_feats=jnp.zeros(
                (config.num_nodes, config.max_neighbors, 1), jnp.float32
            ),
        )
        rng0 = np.random.default_rng(config.train.seed)
        init_ids = jnp.asarray(rng0.integers(0, config.num_nodes, 2), jnp.int32)
        variables = self.model.init(
            jax.random.PRNGKey(config.train.seed),
            dummy_feats, dummy_table, init_ids, init_ids,
        )
        params = variables["params"]
        tx = _make_optimizer(
            config.train, config.total_steps_hint // max(config.train.epochs, 1)
        )
        self.state = TrainState.create(
            apply_fn=self.model.apply, params=params, tx=tx,
            dropout_rng=jax.random.PRNGKey(config.train.seed + 1),
            aux=variables.get("aux"),
            model_state={k: v for k, v in variables.items() if k not in ("params", "aux")} or None,
        )
        if config.node_sharding not in ("replicated", "model"):
            raise ValueError(f"unknown node_sharding {config.node_sharding!r}")
        if config.node_sharding == "model" and config.mesh is None:
            raise ValueError('node_sharding="model" needs a mesh')
        if config.mesh is not None:
            # On a mesh edge batches always shard over the data axis.
            # node_sharding="replicated" keeps every node table whole on
            # every device (plain data parallelism); "model" is
            # config[4]×[5]: node tables (hop features, embedding +
            # moments) partition by node over the model axis — the SAME
            # leaf sharding train_hop_ranker's MP mode uses.
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.mesh import DATA_AXIS, batch_sharding, replicated
            from .train import _node_sharded_state_spec, _node_table_sharding

            mesh = config.mesh
            if config.batch_size % mesh.shape[DATA_AXIS]:
                raise ValueError(
                    f"batch_size {config.batch_size} not divisible by the "
                    f"data axis {mesh.shape[DATA_AXIS]}"
                )
            self._repl = replicated(mesh)
            self._data_shard = batch_sharding(mesh)
            # Dispatch blocks are [super_steps, batch]: the BATCH dim
            # (axis 1) shards over data; the scan dim stays whole.
            block_shard = NamedSharding(mesh, P(None, DATA_AXIS))
            if config.node_sharding == "model":
                if config.num_nodes % mesh.shape[MODEL_AXIS]:
                    raise ValueError(
                        f"num_nodes {config.num_nodes} not divisible by the "
                        f"model axis {mesh.shape[MODEL_AXIS]}"
                    )
                self._nf_shard = _node_table_sharding(mesh)
                self._state_shard = _node_sharded_state_spec(mesh, self.state)
            else:
                self._nf_shard = self._repl
                self._state_shard = self._repl
            self.state = jax.device_put(self.state, self._state_shard)
            # The bare replicated sharding acts as a pytree PREFIX for
            # the NeighborTable argument (train.py precedent) — no
            # per-field spelling to desync if the table grows a field.
            self._dispatch_fn = jax.jit(
                self._train_dispatch,
                in_shardings=(
                    self._state_shard, self._nf_shard, self._repl,
                    block_shard, block_shard, block_shard,
                ),
                out_shardings=(self._state_shard, self._repl, self._repl),
                donate_argnums=(0,),
            )
            self._eval_fn = jax.jit(
                self._eval_mae,
                in_shardings=(
                    self._state_shard, self._nf_shard, self._repl,
                    self._data_shard, self._data_shard, self._data_shard,
                ),
                out_shardings=self._repl,
            )
            self._recycle_fn = jax.jit(
                self._recycle_rows,
                in_shardings=(self._state_shard, self._repl),
                out_shardings=self._state_shard,
                donate_argnums=(0,),
            )
        else:
            # No mesh: one device.  Commit the state once: freshly-created
            # leaves are UNcommitted and the first dispatch would compile
            # a second program the moment the (donated, committed) output
            # comes back for dispatch 2.
            self.state = jax.device_put(self.state, jax.local_devices()[0])
            self._dispatch_fn = jax.jit(
                self._train_dispatch, donate_argnums=(0,)
            )
            self._eval_fn = jax.jit(self._eval_mae)
            self._recycle_fn = jax.jit(
                self._recycle_rows, donate_argnums=(0,)
            )

    # -- ingest: downloads stream -------------------------------------------

    def feed_downloads(
        self, src: np.ndarray, dst: np.ndarray, target: np.ndarray,
        *, block: bool = True,
    ) -> bool:
        """Offer download edges (flat arrays; any length).  Blocks when the
        queue is full — ingest backpressure, like the wire handler."""
        if self.block_source is not None:
            raise RuntimeError(
                "native-ingest adapter attached: downloads must arrive "
                "via the wire adapter, not feed_downloads (the queue "
                "would be silently ignored)"
            )
        try:
            self._downloads.put(
                (
                    np.asarray(src, np.int32),
                    np.asarray(dst, np.int32),
                    np.asarray(target, np.float32),
                ),
                block=block,
            )
            return True
        except queue.Full:
            return False

    def end_of_stream(self) -> None:
        if (
            self._adapter is not None
            and getattr(self._adapter, "_native", None) is not None
        ):
            self._adapter._native.eof()
            return
        self._downloads.put(None)

    def _next_dispatch_block(self, timeout: Optional[float]):
        """Accumulate queued edges into one [super_steps, batch] block
        (static shapes — one compiled program for the whole run).  The
        span's ``wait_s`` is the time spent waiting for input; the rest of
        its duration is the assembly."""
        with default_tracer.span("trainer/next_block") as span:
            block = self._assemble_block(timeout, span)
            span.set(records=0 if block is None else int(block[0].size))
            return block

    def _assemble_block(self, timeout: Optional[float], span):
        if self.block_source is not None:
            t0 = time.perf_counter()
            block = self.block_source(timeout if timeout is not None else 3600.0)
            span.set(wait_s=time.perf_counter() - t0, items=int(block is not None))
            return block
        need = self.config.super_steps * self.config.batch_size
        parts: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        have = 0
        if self._leftover is not None:
            parts.append(self._leftover)
            have = len(self._leftover[0])
            self._leftover = None
        waited, items = 0.0, 0
        while have < need:
            t0 = time.perf_counter()
            try:
                item = self._downloads.get(timeout=timeout)
            except queue.Empty:
                break
            finally:
                waited += time.perf_counter() - t0
            if item is None:
                self._downloads.put(None)  # re-post for other waiters
                break
            parts.append(item)
            items += 1
            have += len(item[0])
        span.set(wait_s=waited, items=items)
        if not parts:
            return None
        es = np.concatenate([p[0] for p in parts])
        ed = np.concatenate([p[1] for p in parts])
        y = np.concatenate([p[2] for p in parts])
        if len(es) < need:
            self._leftover = (es, ed, y)
            return None
        self._leftover = (
            (es[need:], ed[need:], y[need:]) if len(es) > need else None
        )
        shape = (self.config.super_steps, self.config.batch_size)
        return (
            es[:need].reshape(shape), ed[:need].reshape(shape),
            y[:need].reshape(shape),
        )

    # -- ingest: topology stream --------------------------------------------

    def feed_topology(
        self, src: np.ndarray, dst: np.ndarray, rtt: np.ndarray
    ) -> None:
        """Offer probe edges (prober → probed, rtt in seconds-scale units —
        whatever build_neighbor_table should see as the edge feature).
        Only the most recent ``topo_window`` edges count toward the next
        snapshot."""
        part = (
            np.asarray(src, np.int32),
            np.asarray(dst, np.int32),
            np.asarray(rtt, np.float32),
        )
        with self._topo_lock:
            self._topo_parts.append(part)
            self._topo_count += len(part[0])
            self._fed_since_swap += len(part[0])
            # Trim whole parts from the front while the window still holds.
            while (
                self._topo_count - len(self._topo_parts[0][0])
                >= self.config.topo_window
            ):
                dropped = self._topo_parts.pop(0)
                self._topo_count -= len(dropped[0])

    def set_node_features(self, node_feats: np.ndarray) -> None:
        """Refresh the host feature matrix (host-record stream analog);
        picked up at the next snapshot build."""
        self.node_feats = np.asarray(node_feats, np.float32)

    def _drain_window(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        with self._topo_lock:
            parts = list(self._topo_parts)
        if not parts:
            return (
                np.zeros(0, np.int32), np.zeros(0, np.int32),
                np.zeros(0, np.float32),
            )
        src = np.concatenate([p[0] for p in parts])[-self.config.topo_window:]
        dst = np.concatenate([p[1] for p in parts])[-self.config.topo_window:]
        rtt = np.concatenate([p[2] for p in parts])[-self.config.topo_window:]
        return src, dst, rtt

    # -- snapshot refresh ----------------------------------------------------

    def _build_snapshot(self, *, use_source: bool = True) -> None:
        """window + node_feats → neighbor table + hop features (device).
        ``use_source=False`` builds from the CURRENT node_feats — the
        resume path restored them from the checkpoint and a fresh
        adapter's (empty) means must not clobber them."""
        if use_source and self.node_feature_source is not None:
            self.node_feats = np.asarray(
                self.node_feature_source(), np.float32
            )
        src, dst, rtt = self._window
        self.table = build_neighbor_table(
            self.config.num_nodes, src, dst, rtt,
            max_neighbors=self.config.max_neighbors,
        )
        if self.config.node_sharding == "model":
            # The snapshot precompute itself runs NODE-SHARDED on the
            # mesh (halo exchange per hop) — at config[4] scale the
            # [N, F] hop table is the memory wall, so no device ever
            # materializes it whole; the output lands already
            # partitioned for the sharded train step.
            from ..parallel.graph_sharding import (
                build_halo_plan,
                precompute_hop_features_sharded,
            )

            plan = build_halo_plan(self.table, self.config.mesh, axis=MODEL_AXIS)
            self.hop_feats = precompute_hop_features_sharded(
                self.config.mesh,
                jnp.asarray(self.node_feats),
                self.table,
                plan,
                hops=self.config.model.hops,
                axis=MODEL_AXIS,
            )
        else:
            self.hop_feats = _precompute_jit(
                jnp.asarray(self.node_feats), self.table,
                hops=self.config.model.hops,
            )
        if self.config.mesh is not None:
            # Place the snapshot on the mesh ONCE: left on the default
            # device, every dispatch would ship the tables to the other
            # devices again.  (The sharded precompute's output is already
            # partitioned over the model axis.)
            self.table = jax.device_put(self.table, self._repl)
            if self.config.node_sharding == "replicated":
                self.hop_feats = jax.device_put(self.hop_feats, self._repl)
        self.hop_feats.block_until_ready()

    def refresh_snapshot(self) -> Optional[str]:
        """Swap in a snapshot built from the current topology window.
        Returns the new hop-table digest, or None if no topology arrived
        since the last swap (keep serving the old graph rather than pay
        a rebuild for an identical one).  The optimizer, params, LR
        position and dropout stream are untouched."""
        # ``in_flight``: how far the host had run ahead when the refresh
        # began, which the build's reads of the device have to wait out.
        with default_tracer.span(
            "trainer/refresh", in_flight=self._sweep_finished()
        ) as span:
            with self._topo_lock:
                fed = self._fed_since_swap
            window = self._drain_window()
            if fed == 0 or len(window[0]) == 0:
                logger.info("snapshot refresh skipped: no new topology")
                return None
            t0 = time.perf_counter()
            self._window = window
            with self._topo_lock:
                self._fed_since_swap = 0
            self._build_snapshot()
            self.snapshot_idx += 1
            digest = self.snapshot_digest()
            span.set(probe_edges=len(window[0]))
            logger.info(
                "snapshot %d: %d probe edges, hop digest %s (%.2fs)",
                self.snapshot_idx, len(window[0]), digest[:12],
                time.perf_counter() - t0,
            )
            return digest

    def _ensure_snapshot(self) -> None:
        """Build snapshot 0 on first use (the constructor defers it so a
        resume() doesn't pay for a build it immediately replaces)."""
        if self.hop_feats is None:
            self._build_snapshot()

    def snapshot_digest(self) -> str:
        self._ensure_snapshot()
        return hashlib.sha256(
            np.asarray(self.hop_feats).tobytes()
        ).hexdigest()

    # -- node-id lifecycle ---------------------------------------------------

    def request_recycle(self, node_ids: np.ndarray) -> None:
        """Queue recycled dense ids for an embedding/optimizer row reset.
        Thread-safe; the reset itself runs between dispatches on the
        training thread (``apply_pending_recycles``) because the train
        state is donated while a dispatch is in flight."""
        ids = np.asarray(node_ids, np.int32)
        if ids.size:
            with self._recycle_lock:
                self._pending_recycle.append(ids)

    def apply_pending_recycles(self) -> int:
        """Zero the learnable embedding rows AND their Adam moments for
        every id queued by ``request_recycle`` — a recycled id is a NEW
        host and must not inherit its predecessor's learned state.  Rows
        reset to the embedding init's mean (zero), deterministically.
        Returns the number of distinct rows reset."""
        if self._adapter is not None:
            self._adapter.poll_recycled()  # native evictions queue here
        with self._recycle_lock:
            if not self._pending_recycle:
                return 0
            ids = np.unique(np.concatenate(self._pending_recycle))
            self._pending_recycle = []
        mask = np.zeros(self.config.num_nodes, bool)
        mask[ids] = True
        self.state = self._recycle_fn(self.state, jnp.asarray(mask))
        self.nodes_recycled += int(len(ids))
        ONLINE_NODES_RECYCLED.inc(len(ids))
        return int(len(ids))

    def _recycle_rows(self, state, mask):
        """jitted [N]-mask row reset over every node-table leaf — the
        SAME path predicate as the model-parallel sharding spec
        (train._is_node_table_path), so sharded and replicated modes
        reset identically."""
        from .train import _is_node_table_path

        n = self.config.num_nodes

        def zero_rows(path, leaf):
            if (
                _is_node_table_path(path)
                and getattr(leaf, "ndim", 0) >= 1
                and leaf.shape[0] == n
            ):
                bmask = mask.reshape((-1,) + (1,) * (leaf.ndim - 1))
                return jnp.where(bmask, jnp.zeros_like(leaf), leaf)
            return leaf

        return state.replace(
            params=jax.tree_util.tree_map_with_path(zero_rows, state.params),
            opt_state=jax.tree_util.tree_map_with_path(
                zero_rows, state.opt_state
            ),
        )

    # -- train loop ----------------------------------------------------------

    def _train_dispatch(self, state, hop_feats, table, es, ed, y):
        def body(carry, xs):
            b_es, b_ed, b_y = xs
            new_s, loss = _graph_train_step(
                carry, hop_feats, table, b_es, b_ed, b_y, self._query_feats
            )
            return new_s, loss

        rows_before, aux_before = state.rows, state.aux
        state, losses = jax.lax.scan(body, state, (es, ed, y))
        # uint32 arithmetic: a counter that wrapped inside the dispatch
        # still gives the dispatch's own count.
        counted = (
            state.rows - rows_before,
            jax.tree_util.tree_map(lambda a, b: a - b, state.aux, aux_before),
        )
        return state, losses.mean(), counted

    def lower_dispatch(self):
        """The train dispatch lowered at this trainer's shapes (a
        ``jax.stages.Lowered``): nothing compiled yet."""
        cfg = self.config
        self._ensure_snapshot()
        ids = jax.ShapeDtypeStruct((cfg.super_steps, cfg.batch_size), jnp.int32)
        y = jax.ShapeDtypeStruct(ids.shape, jnp.float32)
        return self._dispatch_fn.lower(self.state, self.hop_feats, self.table, ids, ids, y)

    def dispatch_program_text(self) -> str:
        """The compiled train dispatch as text, each instruction with the
        ``op_name`` its scope gave it (``hop/src``, ``stream/moe/experts``,
        ``optimizer``, ...): what maps a device trace's operations back to
        the model (``benchmark/tools/program_trace.py``).  Where the
        compiler left an operation without its scope (the grouped
        products' custom calls, XLA's own copies), ``program_scopes``
        restores it from the same ``lower()``.  Lowers and compiles, or
        loads from the persistent cache, so on demand only."""
        lowered = self.lower_dispatch()
        return program_scopes.restore(
            lowered.compile().as_text(), program_scopes.source_products(lowered)
        )

    def _eval_mae(self, state, hop_feats, table, es, ed, y):
        args = (es, ed) if self._query_feats is None else (es, ed, self._query_feats(ed, y))
        pred = state.apply_fn(
            {"params": state.params, **(state.model_state or {})}, hop_feats, table, *args, train=False
        )
        return jnp.abs(pred - y).mean()

    def eval_mae(self, es, ed, y) -> float:
        """Val MAE against the CURRENT snapshot's hop features."""
        self._ensure_snapshot()
        self.apply_pending_recycles()
        return float(
            self._eval_fn(
                self.state, self.hop_feats, self.table,
                jnp.asarray(es, jnp.int32), jnp.asarray(ed, jnp.int32),
                jnp.asarray(y, jnp.float32),
            )
        )

    @property
    def dispatches_in_flight(self) -> int:
        """Dispatches enqueued and not yet seen finished (as of the last
        sweep: ``run()`` sweeps once a loop turn)."""
        return len(self._in_flight)

    def _sweep_finished(self, wait: bool = False) -> int:
        """Fold the dispatches the device has finished into the ledger,
        oldest first; returns how many are still in flight.  Waits for
        nothing unless ``wait``: a dispatch's loss is ready when the
        dispatch is, and its count came out with it."""
        while self._in_flight and (wait or self._in_flight[0][1].is_ready()):
            index, _loss, rows, aux, span = self._in_flight.popleft()
            trained = int(rows)
            self.records_trained += trained
            self.dispatches_completed = index + 1
            ONLINE_RECORDS_TRAINED.inc(trained)
            if self._fold_aux is not None:
                self._fold_aux(jax.tree_util.tree_map(np.asarray, aux), span)
        ONLINE_DISPATCHES_IN_FLIGHT.set(len(self._in_flight))
        return len(self._in_flight)

    def run(
        self, *, max_dispatches: Optional[int] = None, idle_timeout: float = 1.0,
    ) -> int:
        """Consume the downloads stream until end_of_stream/idle; refresh
        the graph snapshot every ``refresh_every`` dispatches from the
        topology stream.  Returns dispatches run.

        One ``trainer/run`` span holds the call and every phase span
        inside it (DESIGN.md §21); its own time is the loop's
        bookkeeping."""
        cfg = self.config
        with default_tracer.span("trainer/run", compiles=0) as root:
            if self._run_attrs is not None:
                root.set(**self._run_attrs())
            enqueued0, trained0 = self.records_seen, self.records_trained
            in_flight_max = 0
            self._ensure_snapshot()
            ran = 0
            while max_dispatches is None or ran < max_dispatches:
                block = self._next_dispatch_block(timeout=idle_timeout)
                if block is None:
                    break
                self._sweep_finished()
                # Chaos seam: the trainer-crash drill SIGKILLs here at a
                # deterministic dispatch index — after the previous
                # checkpoint committed, before this block trains.
                from ..utils import faultinject

                faultinject.fire("trainer.dispatch")
                # Dispatch span (flight recorder, DESIGN.md §21): one per
                # trained block, so online-training stalls line up against
                # the download/announce traces feeding them.  It closes
                # when the block is ENQUEUED; the ledger says when the
                # device has finished it.
                with default_tracer.span(
                    "trainer/dispatch", dispatch=self.dispatch,
                    records=int(block[0].size),
                ) as dispatch_span:
                    with default_tracer.span("trainer/recycle"):
                        self.apply_pending_recycles()
                    es, ed, y = block
                    with default_tracer.span("trainer/h2d"):
                        es_d, ed_d, y_d = (
                            jnp.asarray(es), jnp.asarray(ed), jnp.asarray(y)
                        )
                    with default_tracer.span("trainer/enqueue"):
                        self.state, self.last_loss, (rows, aux) = self._dispatch_fn(
                            self.state, self.hop_feats, self.table,
                            es_d, ed_d, y_d,
                        )
                # Ask for the count now, so that the sweep that finds the
                # dispatch finished finds the number on the host too (a
                # blocking read of a ready scalar cost 1.4 ms on the v5e).
                for counted in jax.tree_util.tree_leaves((rows, aux)):
                    counted.copy_to_host_async()
                self._in_flight.append(
                    (self.dispatch, self.last_loss, rows, aux, dispatch_span)
                )
                in_flight_max = max(in_flight_max, len(self._in_flight))
                self.dispatches_in_flight_max = max(
                    self.dispatches_in_flight_max, in_flight_max
                )
                ONLINE_DISPATCHES_IN_FLIGHT.set(len(self._in_flight))
                self.dispatch += 1
                ran += 1
                self.records_seen += es.size
                ONLINE_RECORDS_ENQUEUED.inc(es.size)
                if cfg.refresh_every and self.dispatch % cfg.refresh_every == 0:
                    self.refresh_snapshot()
                if (
                    self.checkpoint_dir
                    and cfg.checkpoint_every
                    and self.dispatch % cfg.checkpoint_every == 0
                ):
                    self.checkpoint()
            # Resets queued after the last dispatch must not linger: an
            # eval/export/checkpoint after run() returns would otherwise
            # score recycled ids with their previous owner's embedding.
            self.apply_pending_recycles()
            self._sweep_finished()
            root.set(
                dispatches=ran,
                records_enqueued=self.records_seen - enqueued0,
                records_trained=self.records_trained - trained0,
                in_flight_max=in_flight_max,
            )
        return ran

    # -- checkpoint / resume -------------------------------------------------

    def _ckpt_path(self) -> str:
        return os.path.join(os.path.abspath(self.checkpoint_dir), "online_graph")

    def _payload(self):
        # The pending probe buffer feeds the NEXT drain — without it a
        # resumed run would rebuild a different window at the following
        # refresh than the uninterrupted run (measured: byte-identity
        # broke exactly there).
        with self._topo_lock:
            parts = list(self._topo_parts)
        if parts:
            pend = tuple(
                np.concatenate([p[i] for p in parts]) for i in range(3)
            )
        else:
            pend = (
                np.zeros(0, np.int32), np.zeros(0, np.int32),
                np.zeros(0, np.float32),
            )
        src, dst, rtt = self._window
        # Adapter id-mapping state: clock-driven eviction makes the
        # mapping non-replayable, so it must travel with the checkpoint.
        # Live adapter wins; else carry a restored-but-unclaimed stash
        # forward; else empty-table defaults (same as a fresh adapter).
        ad = self._adapter
        if ad is not None:
            # Consistent pair: the mapping snapshot must not include an
            # eviction whose row reset is still queued (a restore would
            # resurrect the previous owner's embedding/moments).
            ad_state = ad.snapshot_for_checkpoint()
        elif self._adapter_restore is not None:
            ad_state = dict(self._adapter_restore)
        else:
            # No adapter: 1-element sentinel arrays (restore detects the
            # real thing by adapter_id_table's length) — batch-fed
            # trainers don't pay MB-scale dead payload per checkpoint.
            ad_state = {
                "adapter_id_table": np.full(1, -2, np.int32),
                "adapter_bucket_of": np.full(1, -1, np.int64),
                "adapter_last_seen": np.zeros(1, np.float64),
                "adapter_free": np.full(1, -1, np.int64),
                "adapter_next_id": 0,
                "adapter_feat_sum": np.zeros((1, 1), np.float32),
                "adapter_feat_cnt": np.zeros(1, np.float32),
                "adapter_overflow_edges": 0,
                "adapter_evicted_nodes": 0,
            }
        carried = {} if self.state.model_state is None else {"model_state": self.state.model_state}
        return {
            **ad_state,
            **carried,
            "pending_src": pend[0],
            "pending_dst": pend[1],
            "pending_rtt": pend[2],
            "params": self.state.params,
            "opt_state": self.state.opt_state,
            "step": jnp.asarray(self.state.step, jnp.int32),
            "dropout_rng": self.state.dropout_rng,
            "dispatch": self.dispatch,
            "snapshot_idx": self.snapshot_idx,
            "records_seen": self.records_seen,
            "fed_since_swap": self._fed_since_swap,
            # Derived-state inputs: the snapshot is rebuilt from these at
            # restore (build_neighbor_table seeds its sampler, so the
            # rebuild is bit-identical), instead of checkpointing the
            # [N, F] hop table itself.
            "window_src": src,
            "window_dst": dst,
            "window_rtt": rtt,
            "node_feats": self.node_feats,
        }

    def checkpoint(self) -> None:
        import orbax.checkpoint as ocp

        with default_tracer.span(
            "trainer/checkpoint", in_flight=self._sweep_finished()
        ) as span:
            # Queued row resets are not part of the payload — fold them into
            # the state now so a restore cannot resurrect a recycled id's
            # previous-owner embedding/moments.
            self.apply_pending_recycles()
            payload = self._payload()
            span.set(bytes=sum(
                int(getattr(leaf, "nbytes", 0))
                for leaf in jax.tree_util.tree_leaves(payload)
            ))
            ckptr = ocp.StandardCheckpointer()
            ckptr.save(self._ckpt_path(), payload, force=True)
            ckptr.wait_until_finished()

    def make_wire_adapter(self) -> "WireIngestAdapter":
        """An adapter TrainerService(online_sink=...) feeds straight off
        the Train stream — the full wire → online-trainer path."""
        return WireIngestAdapter(self)

    def close(self) -> None:
        """Release stream-side resources (the wire adapter's native
        engine, if any).  Training state is unaffected — checkpoint
        first if it matters.  Closes the ledger too: here, and only here,
        it waits for the dispatches still in flight, so that after a
        close ``records_trained`` and its counter are final."""
        self._sweep_finished(wait=True)
        if self._adapter is not None:
            self._adapter.close()

    def resume(self) -> bool:
        """Restore params/opt/step/stream position AND rebuild the graph
        snapshot from the checkpointed topology window; False if no
        checkpoint exists.  A resumed run continues byte-identically —
        including when the checkpoint straddles a refresh boundary."""
        import orbax.checkpoint as ocp

        if not self.checkpoint_dir or not os.path.exists(self._ckpt_path()):
            return False
        ckptr = ocp.StandardCheckpointer()
        abstract = self._payload()
        # Window length varies run to run — restore against the saved
        # shapes, not the current ones.
        meta = ckptr.metadata(self._ckpt_path()).item_metadata.tree
        for k in (
            "window_src", "window_dst", "window_rtt",
            "pending_src", "pending_dst", "pending_rtt",
        ):
            abstract[k] = np.zeros(meta[k].shape, abstract[k].dtype)
        # Adapter arrays restore against their SAVED shapes (sentinel
        # 1-element when no adapter was attached); checkpoints from
        # before the adapter rode along restore fine without them.
        for k in [k for k in abstract if k.startswith("adapter_")]:
            if k not in meta:
                del abstract[k]
            elif hasattr(abstract[k], "dtype"):
                abstract[k] = np.zeros(meta[k].shape, abstract[k].dtype)
        abstract["node_feats"] = np.zeros(
            meta["node_feats"].shape, np.float32
        )
        restored = ckptr.restore(self._ckpt_path(), abstract)
        # step restores as a STRONG int32 scalar — a weak Python int would
        # compile a different XLA program than the mid-run state's (the
        # byte-identity lesson from the r3 soak).
        self.state = self.state.replace(
            params=restored["params"],
            opt_state=restored["opt_state"],
            step=jnp.asarray(restored["step"], jnp.int32),
            dropout_rng=jnp.asarray(restored["dropout_rng"], jnp.uint32),
            model_state=restored.get("model_state", self.state.model_state),
        )
        self.dispatch = int(restored["dispatch"])
        self.snapshot_idx = int(restored["snapshot_idx"])
        self.records_seen = int(restored["records_seen"])
        # A checkpoint holds a state that everything enqueued has reached,
        # so the ledger restarts level with the host's totals.
        self._in_flight.clear()
        self.records_trained = self.records_seen
        self.dispatches_completed = self.dispatch
        self.node_feats = np.asarray(restored["node_feats"], np.float32)
        self._window = (
            np.asarray(restored["window_src"], np.int32),
            np.asarray(restored["window_dst"], np.int32),
            np.asarray(restored["window_rtt"], np.float32),
        )
        pend = (
            np.asarray(restored["pending_src"], np.int32),
            np.asarray(restored["pending_dst"], np.int32),
            np.asarray(restored["pending_rtt"], np.float32),
        )
        with self._topo_lock:
            self._topo_parts = [pend] if len(pend[0]) else []
            self._topo_count = len(pend[0])
            self._fed_since_swap = int(restored["fed_since_swap"])
        # Stash the adapter id-mapping for the next make_wire_adapter()
        # (or re-attach it to an already-live adapter in place).  A
        # sentinel-length id table means no adapter state was saved.
        from ..records.features import NUM_HASH_BUCKETS

        saved_table = restored.get("adapter_id_table")
        if saved_table is not None and len(saved_table) == NUM_HASH_BUCKETS:
            self._adapter_restore = {
                k: restored[k] for k in restored if k.startswith("adapter_")
            }
            if self._adapter is not None:
                self._adapter._apply_restore(self._adapter_restore)
        else:
            self._adapter_restore = None
        self._build_snapshot(use_source=False)
        return True
