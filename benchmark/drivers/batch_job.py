"""The batch path: the job ``TrainerService.close_and_train()`` runs at
end of stream (``train_hop_ranker`` / ``train_gat_ranker``, chosen by the
configuration), called again as soon as it returns.

Cell parameters (``driver_params``): batch_size, check_steps.  A job is
one of the cell's record blocks: its own tenth held out, one epoch.
"""

from __future__ import annotations

import time

import numpy as np

from .online import Window, _seed32


def split_order(n_records: int, seed: int, batch: int):
    """The rows of each step of a one-epoch job over ``n_records``, as the
    train loop is specified to draw them: a tenth held out by one
    permutation of the seed, the rest shuffled by a second, whole batches
    only.  Returns (train rows in order [steps, batch], all train rows)."""
    order = np.random.default_rng(seed).permutation(n_records)
    n_val = max(int(n_records * 0.1), 1)
    train = order[n_val:]
    shuffled = np.random.default_rng(seed + 0).permutation(train)
    steps = len(train) // batch
    return shuffled[: steps * batch].reshape(steps, batch), train


def records_for_steps(steps: int, batch: int) -> int:
    """The smallest record count whose train split makes ``steps`` steps."""
    n = -(-steps * batch * 10 // 9)
    while n - max(int(n * 0.1), 1) < steps * batch:
        n += 1
    return n


def reference_inputs(ctx) -> dict:
    """What the plain reference needs to follow the check job: its rows in
    the order the job trains on them, and the mean target of its train
    split (the output bias starts there)."""
    import jax

    p, seed = ctx.cell["driver_params"], _seed32(ctx.seed)
    batch, steps = int(p["batch_size"]), int(p["check_steps"])
    n_check = records_for_steps(steps, batch)
    rows, train_rows = split_order(n_check, seed, batch)
    if rows.shape[0] != steps:
        raise RuntimeError(f"{n_check} records make {rows.shape[0]} steps, wanted {steps}")
    src, dst, y = ctx.inputs.blocks[0]
    init_key, dropout_key = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "node_feats": ctx.inputs.node_feats, "topo": ctx.inputs.topo,
        "max_neighbors": ctx.config["graph"]["max_neighbors"],
        "batches": (src[rows], dst[rows], y[rows]),
        "init_key": init_key, "dropout_key": dropout_key,
        "bias_shift": float(y[:n_check][train_rows].mean()),
    }


class Session:
    def __init__(self, ctx) -> None:
        import jax

        from dragonfly2_tpu.models.gnn import build_neighbor_table
        from dragonfly2_tpu.trainer.train import TrainConfig

        p, graph = ctx.cell["driver_params"], ctx.config["graph"]
        self.batch = int(p["batch_size"])
        self.seed = _seed32(ctx.seed)
        table = build_neighbor_table(
            graph["num_nodes"], *ctx.inputs.topo, max_neighbors=graph["max_neighbors"]
        )
        entry, feats, more = ctx.config_module.batch_job(
            ctx.config["model"], ctx.inputs.node_feats, table
        )
        model_config = ctx.config_module.model_config(ctx.config["model"])
        src, dst, y = ctx.inputs.blocks[0]

        def job(n: int, log_every: int):
            return entry(
                feats, table, src[:n], dst[:n], y[:n],
                model_config=model_config,
                config=TrainConfig(epochs=1, seed=self.seed, log_every=log_every),
                batch_size=self.batch, **more,
            )

        self._job = lambda: job(len(src), TrainConfig().log_every)
        self.steps_per_job = split_order(len(src), self.seed, self.batch)[0].shape[0]

        # The first steps, through the entry point the window calls, at its
        # batch and graph: a job cut to ``check_steps`` steps, logging every
        # one so that each step's loss comes back.
        check_steps = int(p["check_steps"])
        n_check = records_for_steps(check_steps, self.batch)
        state, _, history = job(n_check, 1)
        if len(history) != check_steps:
            raise RuntimeError(
                f"the check job logged {len(history)} steps, wanted {check_steps}"
            )
        self._first_state = (
            [h["loss"] for h in history],
            jax.tree_util.tree_map(np.asarray, state.params),
            jax.tree_util.tree_map(np.asarray, state.opt_state),
        )
        self.reference_inputs = reference_inputs(ctx)
        del state
        # One whole job, so that the window compiles nothing.
        self.warm_metrics = self._job()[1]

    def first_readings(self, init_params) -> dict:
        """A job makes its own weights and hands back only the trained
        ones, so its change is taken from the reference's initial weights,
        which are the same draw from the same seed."""
        from .. import check

        losses, params, opt_state = self._first_state
        return check.program_readings(losses, init_params, params, opt_state)

    def run_window(self, seconds: float) -> Window:
        import jax
        from jax.profiler import TraceAnnotation

        walls, metrics = [], None
        start = time.perf_counter()
        while time.perf_counter() - start < seconds:
            t0 = time.perf_counter()
            with TraceAnnotation("bench/job"):
                state, metrics, _ = self._job()
                jax.block_until_ready(state.params)
            walls.append(time.perf_counter() - t0)
            del state
        elapsed = time.perf_counter() - start
        steps = len(walls) * self.steps_per_job
        return Window(
            records=steps * self.batch, steps=steps, launches=steps, elapsed_s=elapsed,
            extras={
                "unit_walls_s": walls, "launches_per_unit": self.steps_per_job,
                "steps_per_launch": 1,
                "repeat_mae": (float(self.warm_metrics.mae), float(metrics.mae)),
            },
        )

    def release(self) -> None:
        self._job = None


def setup(ctx) -> Session:
    return Session(ctx)
