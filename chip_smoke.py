"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

    python chip_smoke.py        # on a machine with a TPU; exit 0 = all stages ok

One process drives the trainer's main path once through the entry points a
user would call, at the full width of the flagship hop ranker (100k-node
graph, K=16, hidden 1024 with dropout, 131,072-edge batches — the shape
``BASELINE.json`` names; only the number of steps is cut),
with random weights made from a seed:

- **A** the shipped loop as shipped: swarm simulation → ``Storage`` shards →
  ``TrainerService`` train stream → both models registered → each artifact
  loaded the way ``scheduler/model_loader.py`` loads it → one announce's
  candidates ranked through ``MLEvaluator``;
- **B** the flagship at full width: (i) ``train_hop_ranker`` for one short
  epoch, (ii) ``OnlineGraphTrainer`` — dispatches, a snapshot refresh, a
  checkpoint, a fresh trainer resumed from it — with the first forward
  checked against the same program jitted for the host CPU, (iii) one
  dispatch fed through the wire adapter, naming the ingest engine it got.
  On a host with four chips it also runs (ii) on two mesh layouts and
  checks that they agree with the one-device run and fill every device;
- **C** every Pallas kernel in ``dragonfly2_tpu/ops`` compiled for the chip
  (``interpret=False``) and checked against its jnp oracle.

Without a TPU it exits non-zero before any stage and prints no result; a
stage that raises ends the run non-zero.  The last line of standard output
is the verdict, one JSON object with exactly these keys:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The line before it, ``chip_smoke: report: {...}``, carries what the stages
saw; every "seconds" in it is a smoke timing taken once, compilation
included — not a metric.

The stages are plain functions that take sizes, so tests call them tiny on
the CPU (``tests/test_chip_smoke.py``).
"""

from __future__ import annotations

import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

# The flagship shape (BASELINE.json).
NUM_HOSTS = 100_000
MAX_NEIGHBORS = 16
HIDDEN = 1024
BATCH = 131_072


class CompileLog:
    """Every XLA program the process loads while the block is open, by
    name, with the seconds it took (a compile, or a persistent-cache
    read) — from JAX's own monitoring events."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        self.programs: list = []  # (fun_name, seconds)
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event == self._COMPILE:
            self.programs.append((str(kw.get("fun_name", "?")), seconds))

    def _on_event(self, event: str, **kw) -> None:
        if event == self._HIT:
            self.cache_hits += 1
        elif event == self._MISS:
            self.cache_misses += 1

    def __enter__(self) -> "CompileLog":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    @property
    def count(self) -> int:
        return len(self.programs)

    @property
    def seconds(self) -> float:
        return sum(s for _, s in self.programs)

    def named(self, fun_name: str, since: int = 0) -> int:
        """Programs loaded after the first ``since`` whose name (as in
        ``jit(<lambda>)``) contains ``fun_name``."""
        return sum(1 for n, _ in self.programs[since:] if fun_name in n)


def _check_losses(losses) -> None:
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")


def _rel_l2(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-12))


# ---------------------------------------------------------------------------
# Stage A — the shipped loop, as shipped
# ---------------------------------------------------------------------------


class _ScoreRecorder:
    """Wraps a loaded scorer so the smoke can see the scores the evaluator
    ranked by (``evaluate_parents`` returns only the order, and falls back
    to the rule scores when the scorer raises — which must not pass)."""

    def __init__(self, scorer) -> None:
        self._scorer = scorer
        self.scores = None

    def __getattr__(self, name):
        return getattr(self._scorer, name)

    def score(self, features, **buckets):
        self.scores = np.asarray(self._scorer.score(features, **buckets))
        return self.scores


def stage_a_shipped_loop(workdir: str, *, downloads: int = 400, hosts: int = 32) -> dict:
    """What ``cli.scheduler --simulate N`` then ``cli.trainer --train-once
    DIR`` do, in one process, and then what the scheduler does with the
    two models that come out."""
    from dragonfly2_tpu.manager.registry import ModelRegistry
    from dragonfly2_tpu.records.storage import Storage
    from dragonfly2_tpu.scheduler import MLEvaluator
    from dragonfly2_tpu.sim import SwarmConfig, SwarmSimulator
    from dragonfly2_tpu.sim.swarm import build_announce_swarm
    from dragonfly2_tpu.trainer.export import load_scorer
    from dragonfly2_tpu.trainer.service import (
        GNN_MODEL_NAME,
        MLP_MODEL_NAME,
        TrainerService,
    )

    # cli/scheduler.py --simulate
    records = os.path.join(workdir, "records")
    storage = Storage(records)
    sim = SwarmSimulator(storage, config=SwarmConfig(num_hosts=hosts, seed=0))
    done = sim.run_downloads(downloads)
    sim.run_probe_rounds(1)
    sim.snapshot_topology()
    storage.flush()

    # cli/trainer.py --train-once
    scheduler_id = "scheduler-local"
    registry = ModelRegistry()
    service = TrainerService(registry)
    session = service.open_train_stream(
        ip="127.0.0.1", hostname=os.uname().nodename, scheduler_id=scheduler_id
    )
    for path in sorted(glob.glob(os.path.join(records, "download*.dfc"))):
        session.send_download_shard(path)
    for path in sorted(glob.glob(os.path.join(records, "networktopology*.dfc"))):
        session.send_network_topology_shard(path)
    run = service.runs[session.close_and_train()]
    if run.error:
        raise AssertionError(f"training run failed: {run.error}")
    registered = {registry.get(mid).name: registry.get(mid) for mid in run.models}
    if set(registered) != {MLP_MODEL_NAME, GNN_MODEL_NAME}:
        raise AssertionError(f"registered {sorted(registered)}, want both models")

    # scheduler/model_loader.py: the ACTIVE model's artifact, digest-checked
    # by the registry, becomes the evaluator's scorer.  One announce: a
    # child and 16 candidate parents from the same 32 hosts the models
    # were trained on.
    task, peers = build_announce_swarm(num_hosts=hosts, seed=0)
    child, parents = peers[0], peers[1:17]
    out = {"downloads": done, "download_rows": run.download_rows,
           "topology_rows": run.topology_rows, "models": {}}
    for name, model in registered.items():
        registry.activate(model.id)
        active = registry.active_model(scheduler_id, name)
        recorder = _ScoreRecorder(load_scorer(registry.load_artifact(active)))
        evaluator = MLEvaluator()
        evaluator.set_scorer(recorder)
        ranked = evaluator.evaluate_parents(parents, child, task.total_piece_count)
        scores = recorder.scores
        if scores is None or len(scores) != len(parents):
            raise AssertionError(f"{name}: the scorer did not score the announce")
        if not np.isfinite(scores).all() or np.ptp(scores) == 0:
            raise AssertionError(f"{name}: scores not finite and varied: {scores}")
        order = np.argsort(-scores, kind="stable")
        if [p.id for p in ranked] != [parents[i].id for i in order]:
            raise AssertionError(f"{name}: ranking does not follow the scores")
        out["models"][name] = {
            "version": active.version,
            "val_log_mae": round(run.metrics[name].mae, 4),
            "score_min": float(scores.min()),
            "score_max": float(scores.max()),
        }
    return out


# ---------------------------------------------------------------------------
# Stage B — the flagship at full width
# ---------------------------------------------------------------------------


def _edges(cluster, seed: int, n: int):
    """``n`` download edges with their ground-truth log-bandwidth."""
    rng = np.random.default_rng(seed)
    hosts = cluster.num_hosts
    src = rng.integers(0, hosts, n).astype(np.int32)
    dst = (src + rng.integers(1, hosts, n).astype(np.int32)) % hosts
    y = np.log1p(cluster._bandwidth_vec(src, dst, rng=rng)).astype(np.float32)
    return src, dst, y


def _probe_sweep(cluster, seed: int, max_neighbors: int):
    """One probe sweep ≈ the neighbor table's capacity (prober → probed,
    rtt in seconds)."""
    rng = np.random.default_rng(seed)
    hosts = cluster.num_hosts
    n = hosts * max_neighbors
    src = rng.integers(0, hosts, n)
    dst = rng.integers(0, hosts, n)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    rtt = cluster._rtt_vec(src, dst, rng=rng) / 1e9
    return src.astype(np.int32), dst.astype(np.int32), rtt.astype(np.float32)


def stage_b_train_loop(
    cluster, compiles: CompileLog, *, max_neighbors: int, hidden: int,
    batch: int, batches: int = 6, learning_rate: float = 3e-4,
    warmup_steps: int = 100,
) -> dict:
    """(i) ``train_hop_ranker`` — the loop ``TrainerService`` calls at end of
    stream — for one epoch of ``batches`` full batches.  The schedule
    defaults to ``TrainConfig``'s: at hidden 1024 a shorter warm-up
    overshoots within three steps."""
    from dragonfly2_tpu.models import HopConfig, build_neighbor_table
    from dragonfly2_tpu.trainer.train import TrainConfig, train_hop_ranker

    src, dst, rtt = _probe_sweep(cluster, 0, max_neighbors)
    table = build_neighbor_table(
        cluster.num_hosts, src, dst, rtt, max_neighbors=max_neighbors
    )
    # A tenth of the edges is held out for validation; the rest must make
    # exactly ``batches`` full batches.
    n_edges = -(-batches * batch * 10 // 9) + 10
    e_src, e_dst, target = _edges(cluster, 1, n_edges)
    first = compiles.count
    _, metrics, history = train_hop_ranker(
        cluster._host_feature_matrix(), table, e_src, e_dst, target,
        model_config=HopConfig(hidden=hidden),
        config=TrainConfig(
            epochs=1, log_every=1, learning_rate=learning_rate,
            warmup_steps=warmup_steps,
        ),
        batch_size=batch,
    )
    losses = [h["loss"] for h in history]
    if len(losses) != batches:
        raise AssertionError(f"ran {len(losses)} steps, want {batches}")
    _check_losses(losses)
    # The loop jits its step as a lambda: one program for one shape.  A
    # second would mean the step compiled again after its first dispatch.
    step_programs = compiles.named("<lambda>", since=first)
    if step_programs != 1:
        raise AssertionError(f"train step compiled {step_programs} times, want 1")
    if not np.isfinite(metrics.mae):
        raise AssertionError(f"validation MAE {metrics.mae}")
    return {"steps": len(losses), "loss_first": losses[0], "loss_last": losses[-1],
            "val_log_mae": round(metrics.mae, 4)}


def _online_config(
    cluster, *, max_neighbors: int, hidden: int, batch: int, super_steps: int,
    mesh=None, node_sharding: str = "replicated",
):
    """The flagship's online-trainer configuration at the given sizes; the
    training schedule is ``TrainConfig``'s default."""
    from dragonfly2_tpu.models import HopConfig
    from dragonfly2_tpu.trainer.online_graph import OnlineGraphConfig

    return OnlineGraphConfig(
        num_nodes=cluster.num_hosts,
        max_neighbors=max_neighbors,
        batch_size=batch,
        super_steps=super_steps,
        topo_window=cluster.num_hosts * max_neighbors,
        model=HopConfig(hidden=hidden),
        mesh=mesh,
        node_sharding=node_sharding,
    )


def _forward_vs_cpu(trainer, src, dst) -> float:
    """The trainer's forward on its own device(s) against the same program
    jitted for the host CPU; relative L2 distance of the predictions."""
    import jax

    def forward(params, hop_feats, table, s, d):
        return trainer.model.apply({"params": params}, hop_feats, table, s, d)

    args = (trainer.state.params, trainer.hop_feats, trainer.table, src, dst)
    got = np.asarray(jax.jit(forward)(*args))
    on_cpu = jax.device_put(jax.device_get(args), jax.devices("cpu")[0])
    want = np.asarray(jax.jit(forward)(*on_cpu))
    if not np.isfinite(got).all():
        raise AssertionError("non-finite forward output on the device")
    return _rel_l2(got, want)


# bf16 keeps 8 bits of mantissa (one rounding ≈ 0.4%), and the forward
# rounds activations about six times between the hop features and the
# float32 head; two correct implementations that order their sums
# differently stay within a few of those.  A wrong gather or a wrong
# matmul moves the predictions by their own size (relative distance ~1).
FORWARD_REL_L2_MAX = 3e-2


def stage_b_online(
    workdir: str, cluster, compiles: CompileLog, *, max_neighbors: int,
    hidden: int, batch: int, super_steps: int = 4, mesh=None,
    node_sharding: str = "replicated",
) -> dict:
    """(ii) ``OnlineGraphTrainer``: two dispatches, new topology and a
    snapshot refresh, a checkpoint, a fresh trainer resumed from it, one
    more dispatch."""
    import jax

    from dragonfly2_tpu.trainer.online_graph import OnlineGraphTrainer

    per_dispatch = super_steps * batch
    cfg = _online_config(
        cluster, max_neighbors=max_neighbors, hidden=hidden, batch=batch,
        super_steps=super_steps, mesh=mesh, node_sharding=node_sharding,
    )

    def make() -> OnlineGraphTrainer:
        src, dst, rtt = _probe_sweep(cluster, 0, max_neighbors)
        return OnlineGraphTrainer(
            cfg, node_feats=cluster._host_feature_matrix(),
            topo_src=src, topo_dst=dst, topo_rtt=rtt, checkpoint_dir=workdir,
        )

    def dispatch(trainer, d: int) -> tuple:
        """Feed and train dispatch ``d``; (loss, programs loaded)."""
        trainer.feed_downloads(*_edges(cluster, 10_000 + d, per_dispatch))
        before = compiles.count
        if trainer.run(max_dispatches=1, idle_timeout=5.0) != 1:
            raise AssertionError(f"dispatch {d} did not run")
        return float(trainer.last_loss), compiles.count - before

    trainer = make()
    v_src, v_dst, v_y = _edges(cluster, 10_000, 4096)  # head of dispatch 0
    val_mae = trainer.eval_mae(v_src, v_dst, v_y)  # builds snapshot 0
    forward_rel_l2 = _forward_vs_cpu(trainer, v_src, v_dst)
    if forward_rel_l2 > FORWARD_REL_L2_MAX:
        raise AssertionError(
            f"forward differs from the CPU reference: rel L2 {forward_rel_l2:.3e} "
            f"> {FORWARD_REL_L2_MAX}"
        )
    loss0, _ = dispatch(trainer, 0)
    loss1, compiled = dispatch(trainer, 1)
    if compiled:
        raise AssertionError(f"second dispatch loaded {compiled} new programs")
    # Where the trainer's arrays live: the bytes of its state and snapshot
    # that each device holds (summed over the arrays' own shards), beside
    # what the device says it has in use (the CPU backend keeps no
    # memory statistics).
    state_bytes = dict.fromkeys(jax.devices(), 0)
    for leaf in jax.tree_util.tree_leaves(
        (trainer.state, trainer.hop_feats, trainer.table)
    ):
        for shard in leaf.addressable_shards:
            state_bytes[shard.device] += shard.data.nbytes
    bytes_in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()
    ]
    trainer.feed_topology(*_probe_sweep(cluster, 1, max_neighbors))
    if trainer.refresh_snapshot() is None:
        raise AssertionError("snapshot refresh skipped")
    trainer.checkpoint()
    trainer.close()

    resumed = make()
    if not resumed.resume():
        raise AssertionError("no checkpoint to resume from")
    at_resume = (resumed.dispatch, int(resumed.state.step), resumed.snapshot_idx)
    if at_resume != (2, 2 * super_steps, 1):
        raise AssertionError(f"resumed at (dispatch, step, snapshot) {at_resume}")
    loss2, _ = dispatch(resumed, 2)
    if int(resumed.state.step) != 3 * super_steps:
        raise AssertionError(f"step {int(resumed.state.step)} after 3 dispatches")
    resumed.close()
    losses = [loss0, loss1, loss2]
    _check_losses(losses)
    return {"steps": 3 * super_steps, "losses": losses,
            "val_log_mae_before": round(val_mae, 4),
            "forward_rel_l2_vs_cpu": forward_rel_l2,
            "devices_holding_state": sum(1 for b in state_bytes.values() if b),
            "state_bytes_per_device": list(state_bytes.values()),
            "bytes_in_use_per_device": bytes_in_use}


def stage_b_wire_ingest(
    cluster, *, max_neighbors: int, hidden: int, batch: int, super_steps: int = 4,
) -> dict:
    """(iii) One dispatch fed the way ``TrainerService(online_sink=...)``
    feeds it: bucket-keyed rows through the wire adapter.
    ``OnlineGraphConfig.native_ingest`` falls back to the Python adapter
    without a word when the C++ library cannot be built — name the engine
    that ran."""
    from dragonfly2_tpu import native
    from dragonfly2_tpu.records.features import HOST_FEATURE_DIM
    from dragonfly2_tpu.trainer.online_graph import OnlineGraphTrainer

    hosts = cluster.num_hosts
    trainer = OnlineGraphTrainer(
        _online_config(
            cluster, max_neighbors=max_neighbors, hidden=hidden, batch=batch,
            super_steps=super_steps,
        ),
        node_feats=np.zeros((hosts, HOST_FEATURE_DIM), np.float32),
        topo_src=np.zeros(0, np.int32), topo_dst=np.zeros(0, np.int32),
        topo_rtt=np.zeros(0, np.float32),
    )
    adapter = trainer.make_wire_adapter()
    try:
        buckets = cluster._bucket_table()
        src, dst, rtt = _probe_sweep(cluster, 0, max_neighbors)
        adapter.feed_topology_rows(
            np.stack([buckets[src], buckets[dst], rtt], axis=1).astype(np.float32)
        )
        adapter.feed_download_rows(
            cluster.generate_feature_rows(super_steps * batch, seed=2)
        )
        if trainer.refresh_snapshot() is None:  # wire-fed features + topology
            raise AssertionError("wire-fed snapshot was not built")
        if trainer.run(max_dispatches=1, idle_timeout=5.0) != 1:
            raise AssertionError("wire-fed dispatch did not run")
        loss = float(trainer.last_loss)
        if not np.isfinite(loss):
            raise AssertionError(f"wire-fed loss {loss}")
        # Hash buckets collide (100k hosts in 2^20 buckets), so a few
        # hosts share an id; none may be dropped for lack of room.
        if adapter.overflow_edges:
            raise AssertionError(f"{adapter.overflow_edges} edges dropped")
        return {"engine": adapter.engine, "native_available": native.available(),
                "native_build_error": native.build_error(), "loss": loss}
    finally:
        trainer.close()


# One- and four-device runs of (ii) train on the same records from the same
# seeds (dropout bits do not depend on the layout); they differ only in how
# sums are split across devices — loss mean, gradient all-reduce, partial
# products.  Measured on four v5e chips: 2e-6 relative.  A shard in the
# wrong place moves the loss by its own size.
LAYOUT_LOSS_REL_MAX = 1e-4


def check_layout_fills_devices(name: str, res: dict, devices: int) -> None:
    """Nothing may land only on device 0: each of the mesh's devices holds
    the same share of the trainer's state and snapshot (both layouts are
    symmetric), and reports at least that many bytes in use.  An idle
    chip reports a few MB in use on its own, so "more than nothing" would
    prove nothing."""
    state = res["state_bytes_per_device"][:devices]
    held = res["bytes_in_use_per_device"][:devices]
    if res["devices_holding_state"] != devices or len(set(state)) != 1:
        raise AssertionError(
            f"{name}: state bytes per device {state}, want the same "
            f"non-zero share on all {devices}"
        )
    if any(b is None or b < s for b, s in zip(held, state)):
        raise AssertionError(
            f"{name}: bytes in use {held} below the state's share {state}"
        )


def stage_b_mesh_layouts(
    workdir: str, cluster, compiles: CompileLog, single: dict, **sizes
) -> dict:
    """(ii) again on every device of a four-chip host, in two layouts:
    data-parallel with node tables replicated, and (data=2, model=2) with
    the node tables, the snapshot precompute and the embedding's moments
    sharded by node.  Every device must hold its share of the state, and
    the losses must agree with the one-device run."""
    import jax

    from dragonfly2_tpu.parallel.mesh import MeshSpec, create_mesh

    out = {}
    for name, spec, node_sharding in (
        ("data4_replicated", MeshSpec(data=4), "replicated"),
        ("data2_model2_node_sharded", MeshSpec(data=2, model=2), "model"),
    ):
        sub = os.path.join(workdir, name)
        res = stage_b_online(
            sub, cluster, compiles, mesh=create_mesh(spec, jax.devices()[:4]),
            node_sharding=node_sharding, **sizes,
        )
        check_layout_fills_devices(name, res, 4)
        gaps = [abs(a - b) / abs(b) for a, b in zip(res["losses"], single["losses"])]
        if max(gaps) > LAYOUT_LOSS_REL_MAX:
            raise AssertionError(
                f"{name}: losses {res['losses']} vs one device "
                f"{single['losses']} (rel {max(gaps):.3e})"
            )
        res["loss_rel_gap_vs_one_device"] = max(gaps)
        out[name] = res
    return out


# ---------------------------------------------------------------------------
# Stage C — the Pallas kernels compile for the chip
# ---------------------------------------------------------------------------


def check_segment_sum(*, edges: int, feat: int, segments: int, interpret: bool) -> dict:
    """``segment_sum_pallas`` in both precisions against
    ``ops.aggregate.segment_sum``.  exact=False rounds the values to bf16
    once (the one-hot weights are exact): ~2^-9 relative."""
    import jax.numpy as jnp

    from dragonfly2_tpu.ops import segment_sum, segment_sum_pallas

    rng = np.random.default_rng(0)
    vals = rng.normal(size=(edges, feat)).astype(np.float32)
    ids = rng.integers(0, segments, edges).astype(np.int32)
    want = np.asarray(segment_sum(jnp.asarray(vals), jnp.asarray(ids), segments))
    out = {}
    for exact, bound in ((True, 1e-5), (False, 1e-2)):
        got = np.asarray(segment_sum_pallas(
            jnp.asarray(vals), ids, segments, exact=exact, interpret=interpret
        ))
        err = _rel_l2(got, want)
        if got.shape != want.shape or not err <= bound:
            raise AssertionError(
                f"segment_sum_pallas(exact={exact}): rel L2 {err:.3e} > {bound}"
            )
        out["exact" if exact else "bf16"] = err
    return out


class _SlotMatrix:
    """The two things ``FusedMLPScorer`` reads of the columnar host store:
    the slot matrix and its version."""

    def __init__(self, matrix: np.ndarray) -> None:
        self._matrix = matrix
        self._row_version = 1

    def matrix_snapshot(self):
        return self._row_version, self._matrix


def check_fused_score(*, slots: int, cand_block: int, interpret: bool) -> float:
    """``FusedMLPScorer`` at the exported depth 32→64→64→1, one candidate
    per slot: its kernel path against its own split-matmul jnp path (run
    at full float32 matmul precision, so the distance is the kernel's
    error)."""
    import jax

    from dragonfly2_tpu.ops.pallas_score import FusedMLPScorer
    from dragonfly2_tpu.records.features import EDGE_FEATURE_DIM, HOST_FEATURE_DIM

    rng = np.random.default_rng(1)
    store = _SlotMatrix(
        rng.normal(size=(slots, HOST_FEATURE_DIM)).astype(np.float32)
    )
    p_slots = rng.integers(0, slots, slots).astype(np.int32)
    c_slots = rng.integers(0, slots, slots).astype(np.int32)
    edge = rng.normal(size=(slots, EDGE_FEATURE_DIM)).astype(np.float32)
    dims = (2 * HOST_FEATURE_DIM + EDGE_FEATURE_DIM, 64, 64, 1)
    weights = [
        (rng.normal(size=(a, b)).astype(np.float32) / np.sqrt(a),
         rng.normal(size=b).astype(np.float32) * 0.1)
        for a, b in zip(dims[:-1], dims[1:])
    ]

    def score(want_kernel: bool, **how) -> np.ndarray:
        scorer = FusedMLPScorer(
            store, weights, cand_block=cand_block, use_pallas=want_kernel, **how
        )
        # The scorer takes its jnp path for any depth but the exported
        # one, whatever it was asked: jnp against jnp must not pass.
        if scorer._use_pallas != want_kernel:
            raise AssertionError(
                f"fused scorer chose use_pallas={scorer._use_pallas}, "
                f"asked for {want_kernel}"
            )
        return scorer.score(edge, src_buckets=p_slots, dst_buckets=c_slots)

    with jax.default_matmul_precision("highest"):
        want = score(False)
    got = score(True, interpret=interpret)
    err = _rel_l2(got, want)
    if got.shape != (slots,) or not err <= 1e-2:
        raise AssertionError(f"fused score kernel: rel L2 {err:.3e} > 1e-2")
    return err


def check_rule_sum(*, rows: int, interpret: bool) -> float:
    """``rule_weighted_sum``'s kernel against its jnp path."""
    import jax

    from dragonfly2_tpu.ops.pallas_score import rule_weighted_sum

    comp = np.random.default_rng(2).random(size=(rows, 6)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = rule_weighted_sum(comp, use_pallas=False)
    got = rule_weighted_sum(comp, use_pallas=True, interpret=interpret)
    err = _rel_l2(got, want)
    if got.shape != (rows,) or not err <= 1e-2:
        raise AssertionError(f"rule_weighted_sum kernel: rel L2 {err:.3e} > 1e-2")
    return err


def check_slot_rows(*, rows: int, width: int) -> dict:
    """``ops/slot_rows.py``'s two kernels against their oracles at one
    block of the stream ranker's expert layer: bfloat16 rows gathered
    (``jnp.take``, bit for bit), float32 rows added into (the sequential
    sum in slot order, bit for bit; the distance to XLA's ``.at[].add`` is
    reported).  The index plan is ``models/stream._block_plan``'s for 32
    held experts that fill six tenths of the block: groups of ascending
    tokens, a token in several groups, padding rows that name rows the
    held slots name too.  The kernels interpret themselves off a TPU."""
    import jax.numpy as jnp

    from dragonfly2_tpu.models import stream
    from dragonfly2_tpu.ops import slot_rows

    rng = np.random.default_rng(3)
    sizes = rng.multinomial(rows * 6 // 10, np.ones(32) / 32).astype(np.int32)
    tokens = np.concatenate([np.sort(rng.choice(rows, n, replace=False)) for n in sizes])
    plan, _, _, valid = stream._block_plan(
        jnp.int32(0), rows, jnp.cumsum(jnp.asarray(sizes)),
        jnp.asarray(np.pad(tokens, (0, rows)).astype(np.int32)), jnp.ones((tokens.size + rows,)),
    )
    x = jnp.asarray(rng.normal(size=(rows, width)), jnp.bfloat16)
    if not bool((slot_rows.gather_rows(x, plan) == jnp.take(x, plan, axis=0)).all()):
        raise AssertionError("slot_rows.gather_rows is not jnp.take")
    y = rng.normal(size=(rows, width)).astype(np.float32)
    u = np.where(np.asarray(valid)[:, None], rng.normal(size=(rows, width)), 0.0).astype(np.float32)
    got = np.asarray(slot_rows.add_rows(jnp.asarray(y), plan, jnp.asarray(u)))
    xla = np.asarray(jnp.asarray(y).at[plan].add(jnp.asarray(u)))
    for r, row in zip(np.asarray(plan), u):
        y[r] += row
    if not (got == y).all():
        raise AssertionError(
            f"slot_rows.add_rows is not the sum in slot order: off by {np.abs(got - y).max():.3e}"
        )
    return {"held_rows": int(valid.sum()), "add_to_xla_max": float(np.abs(got - xla).max())}


def stage_c_kernels(
    *, edges: int = 1_000_000, feat: int = 128, segments: int = 100_000,
    slots: int = 4096, cand_block: int = 128, interpret: bool = False,
    block_rows: int = 32768, block_width: int = 2048,
) -> dict:
    """Every Pallas kernel in ``dragonfly2_tpu/ops`` through the real
    compiler (``interpret=False`` on the chip) against its jnp oracle;
    the numbers are relative L2 distances."""
    import jax

    return {
        # Every check below ran its kernel (the fused scorer's own choice
        # is asserted in check_fused_score), through Mosaic unless
        # interpreted.
        "path": "pallas-interpret" if interpret else "pallas",
        # What FusedMLPScorer / rule_weighted_sum pick when not told.
        "default_path": "pallas" if jax.default_backend() == "tpu" else "jnp",
        "segment_sum": check_segment_sum(
            edges=edges, feat=feat, segments=segments, interpret=interpret),
        "fused_score": check_fused_score(
            slots=slots, cand_block=cand_block, interpret=interpret),
        "rule_weighted_sum": check_rule_sum(rows=slots, interpret=interpret),
        "slot_rows": check_slot_rows(rows=block_rows, width=block_width),
    }


# ---------------------------------------------------------------------------


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
            f"({dev.device_kind}); no stage run",
            file=sys.stderr,
        )
        return 1
    from dragonfly2_tpu.records.synthetic import SyntheticCluster
    from dragonfly2_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    n_dev = len(jax.devices())
    entries_before = _cache_entries(cache_dir)
    print(
        f"chip_smoke: jax {jax.__version__}, {dev.device_kind} x{n_dev}, "
        f"compile cache {cache_dir} ({entries_before} entries)",
        flush=True,
    )
    sizes = dict(max_neighbors=MAX_NEIGHBORS, hidden=HIDDEN, batch=BATCH)
    stages = {}
    t_start = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as work, \
            CompileLog() as compiles:

        def timed(name, fn, *args, **kw):
            t0, c0, s0 = time.perf_counter(), compiles.count, compiles.seconds
            res = fn(*args, **kw)
            res.update(ok=True, seconds=round(time.perf_counter() - t0, 1),
                       programs=compiles.count - c0,
                       compile_seconds=round(compiles.seconds - s0, 1))
            stages[name] = res
            print(f"chip_smoke: stage {name}: {json.dumps(res)}", flush=True)
            return res

        timed("A", stage_a_shipped_loop, work)
        cluster = SyntheticCluster(num_hosts=NUM_HOSTS, seed=0)
        timed("B_i", stage_b_train_loop, cluster, compiles, **sizes)
        single = timed("B_ii", stage_b_online, os.path.join(work, "one"),
                       cluster, compiles, **sizes)
        timed("B_iii", stage_b_wire_ingest, cluster, **sizes)
        if n_dev >= 4:
            timed("B_mesh", stage_b_mesh_layouts, work, cluster, compiles,
                  single, **sizes)
        # ROADMAP Reach 2: what the flagship cell actually holds at peak.
        peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in jax.devices()]
        print(f"chip_smoke: peak_bytes_in_use after stage B: {peak}", flush=True)
        timed("C", stage_c_kernels)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": n_dev}
    report = {
        "device": device,
        "jax": jax.__version__,
        "cache_dir": cache_dir,
        "cache_entries": {"before": entries_before,
                          "after": _cache_entries(cache_dir)},
        "compile": {"programs": compiles.count,
                    "seconds": round(compiles.seconds, 1),
                    "cache_hits": compiles.cache_hits,
                    "cache_misses": compiles.cache_misses},
        "peak_bytes_in_use_after_B": peak,
        "smoke_seconds": round(time.perf_counter() - t_start, 1),
        "seconds_are": "smoke timings, compilation included; not metrics",
        "stages": stages,
    }
    print(f"chip_smoke: report: {json.dumps(report)}", flush=True)
    # The last line is the verdict and nothing else: exactly these keys.
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
