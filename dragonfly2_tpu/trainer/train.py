"""Train loops: MLP regressor + GraphSAGE/GAT, data-parallel over a mesh.

Fills the reference's stub (trainer/training/training.go:60-99): ``Train``
ran trainGNN ∥ trainMLP with TODO bodies; here both are real JAX loops.

Sharding recipe (scaling-book style): one (data, model) mesh; batches
sharded on ``data``; params replicated; the loss all-reduce and gradient
psum are inserted by XLA from the shardings — no hand-written collectives
in the DP path.  The train step is a single jitted function; donated state
keeps HBM flat.

Evaluation matches the manager registry's schema: MLP → MSE/MAE
(manager/rpcserver/manager_server_v1.go CreateModel mlp evaluation),
GNN → additionally precision/recall/F1 of "good parent" classification
(top-half bandwidth), mirroring model.go's GNN evaluation fields.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state
from jax.sharding import Mesh

from ..models.gnn import GATRanker, GNNConfig, GraphSAGE, NeighborTable
from ..models.mlp import MLPConfig, MLPRegressor
from ..parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    create_mesh,
    replicated,
)
from ..utils.tracing import default_tracer
from .ingest import EdgeBatches


@dataclass
class TrainConfig:
    learning_rate: float = 3e-4
    weight_decay: float = 1e-4
    epochs: int = 5
    warmup_steps: int = 100
    log_every: int = 50
    seed: int = 0


@dataclass
class EvalMetrics:
    """What gets recorded in the model registry (manager model evaluation)."""

    mse: float = 0.0
    mae: float = 0.0                  # log-space MAE
    bandwidth_mae_mbps: float = 0.0   # unlogged, MB/s — BASELINE's headline metric
    precision: float = 0.0
    recall: float = 0.0
    f1: float = 0.0

    def to_dict(self) -> Dict[str, float]:
        return {
            "mse": self.mse,
            "mae": self.mae,
            "bandwidth_mae_mbps": self.bandwidth_mae_mbps,
            "precision": self.precision,
            "recall": self.recall,
            "f1": self.f1,
        }


class TrainState(train_state.TrainState):
    dropout_rng: jax.Array = None
    # Feature standardization constants (computed from the training split,
    # applied at train/eval/serve time; exported into the scorer artifact).
    # Raw features mix log-scales (~20) with [0,1] ratios — unnormalized,
    # the regressor conditions poorly and validation MAE roughly doubles.
    feat_mean: jax.Array = None
    feat_std: jax.Array = None
    # Rows the graph train step has trained on, counted on the device by
    # the step itself: one replicated uint32 scalar that wraps (readers
    # take differences in uint32).  Not part of any checkpoint: the host's
    # running totals are what persist.
    rows: jax.Array = np.uint32(0)
    # Running sums (uint32, wrapping like ``rows``) of what the model sows
    # into its ``aux`` collection each step, in that collection's own
    # structure; None for a model that sows nothing.
    aux: Any = None
    # Collections the model carries from step to step beside its
    # parameters, each updated by the model's own rule inside the step and
    # touched by no gradient or optimizer (an expert router's selection
    # bias), by collection name; None for a model that has none.
    model_state: Any = None


def _huber(pred: jax.Array, target: jax.Array, delta: float = 1.0) -> jax.Array:
    err = pred - target
    abs_err = jnp.abs(err)
    quad = jnp.minimum(abs_err, delta)
    return jnp.mean(0.5 * quad**2 + delta * (abs_err - quad))


def _make_optimizer(cfg: TrainConfig, steps_per_epoch: int) -> optax.GradientTransformation:
    total = max(cfg.epochs * steps_per_epoch, cfg.warmup_steps + 1)
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=cfg.learning_rate,
        warmup_steps=cfg.warmup_steps,
        decay_steps=total,
    )
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(schedule, weight_decay=cfg.weight_decay),
    )


# ---------------------------------------------------------------------------
# MLP (BASELINE configs[0]: correctness + MAE parity on 10k records)
# ---------------------------------------------------------------------------


def _mlp_train_step(state: TrainState, feats, target):
    rng = jax.random.fold_in(state.dropout_rng, state.step)
    feats = (feats - state.feat_mean) / state.feat_std

    def loss_fn(params):
        pred = state.apply_fn(
            {"params": params}, feats, train=True, rngs={"dropout": rng}
        )
        return _huber(pred, target)

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    return state.apply_gradients(grads=grads), loss


def train_mlp(
    train_data: EdgeBatches,
    val_data: EdgeBatches,
    *,
    model_config: Optional[MLPConfig] = None,
    config: Optional[TrainConfig] = None,
    mesh: Optional[Mesh] = None,
) -> Tuple[TrainState, EvalMetrics, List[Dict[str, float]]]:
    cfg = config or TrainConfig()
    mcfg = model_config or MLPConfig()
    mesh = mesh or create_mesh()
    model = MLPRegressor(mcfg)

    # Batch dim shards over the data axis — round the batch to a multiple.
    data_n = mesh.shape[DATA_AXIS]
    if train_data.batch_size % data_n:
        rounded = max((train_data.batch_size // data_n) * data_n, data_n)
        train_data = EdgeBatches(
            train_data.rows,
            batch_size=rounded,
            shuffle=train_data.shuffle,
            seed=train_data.seed,
            drop_remainder=train_data.drop_remainder,
        )
    if len(train_data) == 0:
        # Silently running zero steps would export an untrained (random)
        # model — fail loudly instead.
        raise ValueError(
            f"no full batches: {train_data.rows.shape[0]} rows < batch "
            f"{train_data.batch_size} (data axis {data_n})"
        )

    rng = jax.random.PRNGKey(cfg.seed)
    init_rng, dropout_rng = jax.random.split(rng)
    sample = jnp.zeros((2, mcfg.in_dim), jnp.float32)
    params = model.init(init_rng, sample)["params"]
    from ..models.mlp import warm_start_output_bias

    params = warm_start_output_bias(
        params, float(train_data.rows[:, -1].mean())
    )
    train_feats = train_data.rows[:, 2 : 2 + mcfg.in_dim]
    feat_mean = jnp.asarray(train_feats.mean(axis=0), jnp.float32)
    raw_std = train_feats.std(axis=0)
    # Columns (near-)constant in training carry no signal — scale them by 1,
    # not by a tiny std that would amplify any serve-time deviation into a
    # distribution explosion (e.g. a single-content-length training corpus
    # meeting a different length at scheduling time).
    feat_std = jnp.asarray(np.where(raw_std < 1e-3, 1.0, raw_std), jnp.float32)
    state = TrainState.create(
        apply_fn=model.apply,
        params=params,
        tx=_make_optimizer(cfg, max(len(train_data), 1)),
        dropout_rng=dropout_rng,
        feat_mean=feat_mean,
        feat_std=feat_std,
    )

    data_shard = batch_sharding(mesh)
    repl = replicated(mesh)
    state = jax.device_put(state, repl)
    step = jax.jit(
        _mlp_train_step,
        in_shardings=(repl, data_shard, data_shard),
        out_shardings=(repl, repl),
        donate_argnums=(0,),
    )

    history: List[Dict[str, float]] = []
    for epoch in range(cfg.epochs):
        for feats, target, _, _ in train_data.epoch(epoch):
            state, loss = step(state, jnp.asarray(feats), jnp.asarray(target))
            if int(state.step) % cfg.log_every == 0:
                history.append(
                    {"step": int(state.step), "epoch": epoch, "loss": float(loss)}
                )
    metrics = evaluate_mlp(state, val_data)
    return state, metrics, history


def evaluate_mlp(state: TrainState, val_data: EdgeBatches) -> EvalMetrics:
    # The standardization constants are ARGUMENTS: closed over, the
    # training set's statistics would be baked into the program, and a
    # program that embeds its data never hits the persistent compile cache.
    apply = jax.jit(
        lambda p, mean, std, x: state.apply_fn({"params": p}, (x - mean) / std)
    )
    preds, targets = [], []
    for feats, target, _, _ in val_data.epoch(0):
        preds.append(np.asarray(apply(
            state.params, state.feat_mean, state.feat_std, jnp.asarray(feats)
        )))
        targets.append(target)
    return _regression_metrics(np.concatenate(preds), np.concatenate(targets))


def _regression_metrics(pred: np.ndarray, target: np.ndarray) -> EvalMetrics:
    err = pred - target
    mse = float(np.mean(err**2))
    mae = float(np.mean(np.abs(err)))
    bw_mae = float(np.mean(np.abs(np.expm1(pred) - np.expm1(target)))) / 1e6
    # "Good parent" = top-half bandwidth; measures ranking usefulness the way
    # the registry's gnn evaluation wants precision/recall/f1.
    thresh = np.median(target)
    pos_pred, pos_true = pred >= thresh, target >= thresh
    tp = float(np.sum(pos_pred & pos_true))
    precision = tp / max(float(np.sum(pos_pred)), 1.0)
    recall = tp / max(float(np.sum(pos_true)), 1.0)
    f1 = 2 * precision * recall / max(precision + recall, 1e-9)
    return EvalMetrics(
        mse=mse,
        mae=mae,
        bandwidth_mae_mbps=bw_mae,
        precision=precision,
        recall=recall,
        f1=f1,
    )


# ---------------------------------------------------------------------------
# GraphSAGE (configs[1]): self-supervised RTT regression over the probe graph
# ---------------------------------------------------------------------------


def train_graphsage(
    node_feats: np.ndarray,
    table: NeighborTable,
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    edge_target: np.ndarray,       # e.g. normalized RTT per probe edge
    *,
    model_config: Optional[GNNConfig] = None,
    config: Optional[TrainConfig] = None,
    mesh: Optional[Mesh] = None,
    batch_size: int = 4096,
) -> Tuple[TrainState, EvalMetrics, List[Dict[str, float]]]:
    """Encoder pretraining: predict per-edge RTT from endpoint embeddings.

    The probe graph's signal (EMA RTT per edge) supervises the encoder; the
    learned embeddings are the node representation the GAT ranker and the
    evaluator-facing scorer build on.
    """
    cfg = config or TrainConfig()
    mcfg = model_config or GNNConfig()
    mesh = mesh or create_mesh()

    # Edge head on top of the encoder, defined inline to keep GraphSAGE reusable.
    import flax.linen as nn

    class _SAGEEdgeModel(nn.Module):
        cfg: GNNConfig

        @nn.compact
        def __call__(self, node_feats, table, src, dst, *, train: bool = False):
            emb = GraphSAGE(self.cfg)(node_feats, table, train=train)
            s = jnp.take(emb, src, axis=0)
            d = jnp.take(emb, dst, axis=0)
            x = jnp.concatenate([s, d, s * d], axis=-1).astype(self.cfg.dtype)
            x = nn.gelu(nn.Dense(self.cfg.hidden, dtype=self.cfg.dtype, param_dtype=jnp.float32)(x))
            return nn.Dense(1, dtype=jnp.float32, param_dtype=jnp.float32)(x)[..., 0]

    model = _SAGEEdgeModel(mcfg)
    return _train_graph_model(
        model, node_feats, table, edge_src, edge_dst, edge_target, None,
        cfg, mesh, batch_size,
    )


# ---------------------------------------------------------------------------
# GAT ranker (configs[2]): beats the rule-based evaluator on bandwidth MAE
# ---------------------------------------------------------------------------


def train_gat_ranker(
    node_feats: np.ndarray,
    table: NeighborTable,
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    edge_target: np.ndarray,          # log1p bandwidth per download edge
    query_edge_feats: Optional[np.ndarray] = None,
    *,
    model_config: Optional[GNNConfig] = None,
    config: Optional[TrainConfig] = None,
    mesh: Optional[Mesh] = None,
    batch_size: int = 4096,
) -> Tuple[TrainState, EvalMetrics, List[Dict[str, float]]]:
    from ..models import require_servable

    require_servable(model_config, "train_gat_ranker")
    cfg = config or TrainConfig()
    mcfg = model_config or GNNConfig()
    mesh = mesh or create_mesh()
    model = GATRanker(mcfg)
    return _train_graph_model(
        model, node_feats, table, edge_src, edge_dst, edge_target,
        query_edge_feats, cfg, mesh, batch_size,
    )


def train_hop_ranker(
    node_feats: np.ndarray,
    table: NeighborTable,
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    edge_target: np.ndarray,          # log1p bandwidth per download edge
    query_edge_feats: Optional[np.ndarray] = None,
    *,
    model_config=None,
    config: Optional[TrainConfig] = None,
    mesh: Optional[Mesh] = None,
    batch_size: int = 65_536,
    hop_feats: Optional[np.ndarray] = None,
    node_sharding: str = "replicated",
) -> Tuple[TrainState, EvalMetrics, List[Dict[str, float]]]:
    """Scatter-free flagship ranker (models/hop.py): aggregation is
    precomputed once per snapshot, the train step is pure dense MXU work
    on edge batches — measured ~9× faster per step than the GAT at the
    north-star shape with equal-or-better validation quality
    (BENCHMARKS.md).  Pass ``hop_feats`` when the caller already
    precomputed them (the scorer export needs the same array — compute
    once, use twice).  ``node_sharding="model"`` partitions the hop
    features and embedding table by node over the mesh's model axis —
    the config[4] scale mode where node tables exceed one chip's HBM."""
    from ..models import require_servable
    from ..models.hop import HopConfig, HopRanker, precompute_hop_features_jit

    require_servable(model_config, "train_hop_ranker")
    cfg = config or TrainConfig()
    mcfg = model_config or HopConfig()
    mesh = mesh or create_mesh()
    if hop_feats is None:
        if node_sharding == "model":
            # config[4] scale mode: the [N, F] hop table is the memory
            # wall, so the PRECOMPUTE itself runs node-sharded — per hop
            # one halo all-to-all of boundary rows replaces the full-
            # table gather, and the output lands already sharded
            # P(model) for the train step (no host round-trip).
            from ..parallel.graph_sharding import (
                build_halo_plan,
                precompute_hop_features_sharded,
            )
            from ..parallel.mesh import MODEL_AXIS

            plan = build_halo_plan(table, mesh, axis=MODEL_AXIS)
            hop_feats = precompute_hop_features_sharded(
                mesh,
                jnp.asarray(node_feats, jnp.float32),
                table,
                plan,
                hops=mcfg.hops,
                axis=MODEL_AXIS,
            )
        else:
            # The module-level cached jit (models/hop.py): a per-call
            # jax.jit(partial(...)) here compiled a throwaway program per
            # train_hop_ranker invocation (DF010).
            hop_feats = np.asarray(
                precompute_hop_features_jit(
                    jnp.asarray(node_feats, jnp.float32), table,
                    hops=mcfg.hops,
                )
            )
    model = HopRanker(mcfg)
    return _train_graph_model(
        model, hop_feats, table, edge_src, edge_dst, edge_target,
        query_edge_feats, cfg, mesh, batch_size,
        node_sharding=node_sharding,
    )


def _graph_train_step(state: TrainState, node_feats, table, src, dst, target, qef):
    """``qef``: the query edge features [B, .], None, or a function
    ``(dst, target) -> features`` for a ranker whose features are made of
    the batch itself (models.Ranker.query_feats), so that they are always
    those of the records this step was given."""
    rng = jax.random.fold_in(state.dropout_rng, state.step)
    if callable(qef):
        qef = qef(dst, target)

    carried = dict(state.model_state or {})

    def loss_fn(params):
        args = (node_feats, table, src, dst) if qef is None else (node_feats, table, src, dst, qef)
        # ``aux``: what the model counts about its own step (an expert
        # layer's load); empty for a model that sows nothing.  The carried
        # collections come back as the step's rule left them.
        pred, sown = state.apply_fn(
            {"params": params, **carried}, *args, train=True, rngs={"dropout": rng},
            mutable=["aux", *carried],
        )
        with jax.named_scope("loss"):
            # The count rides out beside the loss: the size of the
            # residual the loss is the mean of, a constant under jit.
            rows = np.uint32(math.prod(np.broadcast_shapes(pred.shape, target.shape)))
            return _huber(pred, target), (rows, sown.get("aux"), {k: sown[k] for k in carried})

    (loss, (rows, aux, moved)), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
    with jax.named_scope("optimizer"):
        new_state = state.apply_gradients(grads=grads)
    sums = state.aux
    if sums is not None:
        sums = jax.tree_util.tree_map(lambda a, b: a + b.astype(a.dtype), sums, aux)
    if moved:
        new_state = new_state.replace(model_state=moved)
    return new_state.replace(rows=state.rows + rows, aux=sums), loss


def _node_table_sharding(mesh: Mesh):
    """THE node-table partition spec: rows sharded over the model axis.
    Single definition — hop features and the embedding/optimizer leaves
    must always shard identically."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import MODEL_AXIS

    return NamedSharding(mesh, P(MODEL_AXIS, None))


def _is_node_table_path(path) -> bool:
    """True for leaves that live in per-node tables — the learnable
    embedding and its optimizer moments (they share the 'embedding' key
    path).  THE single definition: the model-parallel sharding spec and
    the online trainer's id-recycling row reset must agree on which
    leaves are node tables, or a recycled id's state silently survives
    in one of them."""
    return any(getattr(p, "key", None) == "embedding" for p in path)


def _node_sharded_state_spec(mesh: Mesh, tree):
    """Sharding tree for model-parallel node tables: the learnable
    embedding table (and its optimizer moments — they share the leaf
    path) partitions by NODE over the model axis; everything else
    replicates.  The config[4] memory story: at 1B-edge scale the node
    tables are the floor, so they shard instead of replicating."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = NamedSharding(mesh, P())
    node_tables = _node_table_sharding(mesh)

    def leaf_spec(path, leaf):
        if _is_node_table_path(path):
            return node_tables
        return repl

    return jax.tree_util.tree_map_with_path(leaf_spec, tree)


def _train_graph_model(
    model,
    node_feats: np.ndarray,
    table: NeighborTable,
    edge_src: np.ndarray,
    edge_dst: np.ndarray,
    edge_target: np.ndarray,
    query_edge_feats: Optional[np.ndarray],
    cfg: TrainConfig,
    mesh: Mesh,
    batch_size: int,
    node_sharding: str = "replicated",
) -> Tuple[TrainState, EvalMetrics, List[Dict[str, float]]]:
    # One job, one trace: ``train/job`` and its phases (DESIGN.md §21).
    with default_tracer.span("train/job") as job:
        n_edges = len(edge_src)
        with default_tracer.span("train/shuffle"):
            rng = np.random.default_rng(cfg.seed)
            order = rng.permutation(n_edges)
        n_val = max(int(n_edges * 0.1), 1)
        val_idx, train_idx = order[:n_val], order[n_val:]
        b0 = min(batch_size, max(len(train_idx), 2))
        # The batch dim shards over the data axis — round down to a multiple.
        data_n = mesh.shape[DATA_AXIS]
        b0 = max((b0 // data_n) * data_n, data_n)
        if len(train_idx) < b0:
            raise ValueError(
                f"no full batches: {len(train_idx)} train edges < batch {b0} "
                f"(data axis {data_n})"
            )
        has_qef = query_edge_feats is not None

        with default_tracer.span("train/init"):
            jrng = jax.random.PRNGKey(cfg.seed)
            init_rng, dropout_rng = jax.random.split(jrng)
            nf = jnp.asarray(node_feats, jnp.float32)
            sample_args = (
                nf,
                table,
                jnp.zeros((b0,), jnp.int32),
                jnp.zeros((b0,), jnp.int32),
            )
            if has_qef:
                sample_args = sample_args + (jnp.zeros((b0, query_edge_feats.shape[1]), jnp.float32),)
            params = model.init(init_rng, *sample_args)["params"]
            # Output-bias warm start at the training-split target mean (shared fix:
            # models.mlp.warm_start_output_bias — Huber's linear tail otherwise
            # spends the whole run closing the constant offset on short schedules).
            from ..models.mlp import warm_start_output_bias

            params = warm_start_output_bias(params, float(edge_target[train_idx].mean()))

            steps_per_epoch = max(len(train_idx) // b0, 1)
            state = TrainState.create(
                apply_fn=model.apply,
                params=params,
                tx=_make_optimizer(cfg, steps_per_epoch),
                dropout_rng=dropout_rng,
            )

            repl = replicated(mesh)
            data_shard = batch_sharding(mesh)
            if node_sharding == "model":
                # Tensor-parallel node tables (VERDICT r2 weak-#7 made a product
                # option): hop features + the embedding table (and its moments)
                # partition by node over the model axis; the endpoint gathers
                # cross shards and XLA inserts the collectives.  Loss parity with
                # the replicated mode is asserted in tests.
                nf_shard = _node_table_sharding(mesh)
                state_shard = _node_sharded_state_spec(mesh, state)
            elif node_sharding == "replicated":
                nf_shard = repl
                state_shard = repl
            else:
                raise ValueError(f"unknown node_sharding {node_sharding!r}")
            state = jax.device_put(state, state_shard)
            nf = jax.device_put(nf, nf_shard)
            dev_table = jax.device_put(table, repl)

            in_shardings = (state_shard, nf_shard, repl, data_shard, data_shard, data_shard)
            if has_qef:
                in_shardings = in_shardings + (data_shard,)
                step_fn = jax.jit(
                    _graph_train_step,
                    in_shardings=in_shardings,
                    out_shardings=(state_shard, repl),
                    donate_argnums=(0,),
                )
            else:
                step_fn = jax.jit(
                    lambda s, n, t, a, b, y: _graph_train_step(s, n, t, a, b, y, None),
                    in_shardings=in_shardings,
                    out_shardings=(state_shard, repl),
                    donate_argnums=(0,),
                )

        history: List[Dict[str, float]] = []
        steps = 0
        for epoch in range(cfg.epochs):
            with default_tracer.span("train/shuffle"):
                ep_order = np.random.default_rng(cfg.seed + epoch).permutation(train_idx)
            for start in range(0, len(ep_order) - b0 + 1, b0):
                with default_tracer.span("train/batch"):
                    idx = ep_order[start : start + b0]
                    args = [
                        state,
                        nf,
                        dev_table,
                        jnp.asarray(edge_src[idx], jnp.int32),
                        jnp.asarray(edge_dst[idx], jnp.int32),
                        jnp.asarray(edge_target[idx], jnp.float32),
                    ]
                    if has_qef:
                        args.append(jnp.asarray(query_edge_feats[idx], jnp.float32))
                with default_tracer.span("train/step", step=steps):
                    state, loss = step_fn(*args)
                steps += 1
                # Reading the step back waits for the device every step; it
                # stays until a perf_opt PR takes it out, under its own span.
                with default_tracer.span("train/step_sync"):
                    step_now = int(state.step)
                if step_now % cfg.log_every == 0:
                    history.append(
                        {"step": step_now, "epoch": epoch, "loss": float(loss)}
                    )

        # Validation on the held-out edges.
        def predict(idx: np.ndarray) -> np.ndarray:
            args = [
                nf,
                dev_table,
                jnp.asarray(edge_src[idx], jnp.int32),
                jnp.asarray(edge_dst[idx], jnp.int32),
            ]
            if has_qef:
                args.append(jnp.asarray(query_edge_feats[idx], jnp.float32))
            return np.asarray(state.apply_fn({"params": state.params}, *args))

        with default_tracer.span("train/validate"):
            pred = predict(val_idx)
            metrics = _regression_metrics(pred, edge_target[val_idx])
        # ``predict`` has waited for the last step, so this read waits for
        # nothing: the rows the steps counted on the device, exactly.
        job.set(records_trained=int(state.rows), steps=steps)
        return state, metrics, history


# ---------------------------------------------------------------------------
# Checkpointing (orbax) — the reference had nothing to checkpoint; the 10-min
# 1B-record runs need save/restore (SURVEY.md §5.4).
# ---------------------------------------------------------------------------


def save_checkpoint(path: str, state: TrainState) -> None:
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, {"params": state.params, "step": int(state.step)}, force=True)
    ckptr.wait_until_finished()


def restore_params(path: str) -> Any:
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    return ckptr.restore(path)["params"]
