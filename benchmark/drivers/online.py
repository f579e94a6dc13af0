"""The online path: one ``OnlineGraphTrainer``, a producer thread feeding
it blocks through ``feed_downloads(block=True)``, ``run()`` consuming them
as scanned dispatches.  A closed loop with one producer.

Cell parameters (``driver_params``): batch_size, super_steps,
queue_capacity, and ``mesh`` ({"data": n}) for a cell over several chips.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Window:
    records: int
    steps: int
    launches: int
    elapsed_s: float
    extras: dict = field(default_factory=dict)


def _seed32(seed: int) -> int:
    return int(seed) % 2_147_483_629


def reference_inputs(ctx) -> dict:
    """What the plain reference needs to follow the first dispatch."""
    import jax

    p, seed = ctx.cell["driver_params"], _seed32(ctx.seed)
    shape = (int(p["super_steps"]), int(p["batch_size"]))
    return {
        "node_feats": ctx.inputs.node_feats, "topo": ctx.inputs.topo,
        "max_neighbors": ctx.config["graph"]["max_neighbors"],
        "batches": tuple(a.reshape(shape) for a in ctx.inputs.blocks[0]),
        "init_key": jax.random.PRNGKey(seed),
        "dropout_key": jax.random.PRNGKey(seed + 1), "bias_shift": 0.0,
        "mean_loss_only": True,
    }


class Session:
    def __init__(self, ctx) -> None:
        import jax

        from dragonfly2_tpu.trainer.online_graph import OnlineGraphConfig, OnlineGraphTrainer
        from dragonfly2_tpu.trainer.train import TrainConfig

        p, graph = ctx.cell["driver_params"], ctx.config["graph"]
        self.ctx = ctx
        self.super_steps, self.batch = int(p["super_steps"]), int(p["batch_size"])
        need = self.super_steps * self.batch
        if any(len(b[0]) != need for b in ctx.inputs.blocks):
            raise ValueError(f"a block must hold one dispatch: {need} records")
        mesh = None
        if p.get("mesh"):
            from dragonfly2_tpu.parallel.mesh import MeshSpec, create_mesh

            mesh = create_mesh(MeshSpec(data=int(p["mesh"]["data"])), ctx.devices)
        seed = _seed32(ctx.seed)
        self.trainer = OnlineGraphTrainer(
            OnlineGraphConfig(
                num_nodes=graph["num_nodes"], max_neighbors=graph["max_neighbors"],
                batch_size=self.batch, super_steps=self.super_steps,
                refresh_every=0, checkpoint_every=0,
                queue_capacity=int(p["queue_capacity"]),
                model=ctx.config_module.model_config(ctx.config["model"]),
                train=TrainConfig(seed=seed), mesh=mesh, node_sharding="replicated",
            ),
            node_feats=ctx.inputs.node_feats,
            topo_src=ctx.inputs.topo[0], topo_dst=ctx.inputs.topo[1],
            topo_rtt=ctx.inputs.topo[2],
        )
        host = lambda tree: jax.tree_util.tree_map(np.asarray, tree)
        params0 = host(self.trainer.state.params)
        # The first dispatch, through the window's own call and feed; then
        # one more, so that the window meets a state that has been donated
        # and returned once already.
        first = ctx.inputs.blocks[0]
        self._dispatch(first)
        from .. import check

        self.first = check.program_readings(
            [float(self.trainer.last_loss)], params0,
            host(self.trainer.state.params), host(self.trainer.state.opt_state),
        )
        self._dispatch(ctx.inputs.blocks[1 % len(ctx.inputs.blocks)])
        jax.block_until_ready(self.trainer.state.params)
        self.reference_inputs = reference_inputs(ctx)

    def first_readings(self, _reference_init) -> dict:
        return self.first

    def _dispatch(self, block) -> None:
        self.trainer.feed_downloads(*block, block=True)
        if self.trainer.run(max_dispatches=1, idle_timeout=60.0) != 1:
            raise RuntimeError("the trainer took no dispatch from a full block")

    def run_window(self, seconds: float) -> Window:
        import jax
        from jax.profiler import TraceAnnotation

        trainer, blocks = self.trainer, self.ctx.inputs.blocks
        state = {"blocked": 0.0, "error": None}
        start = time.perf_counter()
        deadline = start + seconds

        # A closed loop on completion: a block is fed only while fewer than
        # ``queue_capacity`` + 1 fed blocks are unfinished on the device.
        # ``run()`` itself never waits for the device (its dispatches are
        # asynchronous and the queue only bounds what is not yet enqueued),
        # so a producer held by the queue alone runs tens of dispatches
        # ahead and the window ends long after ``seconds``.
        base, depth = trainer.dispatch, int(self.ctx.cell["driver_params"]["queue_capacity"]) + 1

        def produce() -> None:
            fed = done = 0
            try:
                while time.perf_counter() < deadline:
                    t0 = time.perf_counter()
                    with TraceAnnotation("bench/feed_blocked"):
                        while fed - done >= depth and time.perf_counter() < deadline:
                            # ``last_loss`` is set before ``dispatch`` is
                            # counted, so it belongs to dispatch >= enqueued.
                            enqueued, loss = trainer.dispatch - base, trainer.last_loss
                            if enqueued > done:
                                jax.block_until_ready(loss)
                                done = enqueued
                            else:
                                time.sleep(0.001)
                        if fed - done < depth:
                            trainer.feed_downloads(*blocks[fed % len(blocks)], block=True)
                            fed += 1
                    state["blocked"] += time.perf_counter() - t0
            except BaseException as exc:  # surfaced on the main thread below
                state["error"] = exc
            finally:
                trainer.end_of_stream()

        feeder = threading.Thread(target=produce, name="bench-producer", daemon=True)
        feeder.start()
        with TraceAnnotation("bench/run"):
            ran = trainer.run(idle_timeout=120.0)
        with TraceAnnotation("bench/drain"):
            jax.block_until_ready((trainer.state.params, trainer.last_loss))
        elapsed = time.perf_counter() - start
        feeder.join(timeout=120.0)
        if feeder.is_alive():
            raise RuntimeError("the producer did not stop")
        if state["error"] is not None:
            raise state["error"]
        steps = ran * self.super_steps
        return Window(
            records=steps * self.batch, steps=steps, launches=ran, elapsed_s=elapsed,
            extras={"producer_blocked_s": state["blocked"], "steps_per_launch": self.super_steps},
        )

    def release(self) -> None:
        self.trainer.close()
        self.trainer = None


def setup(ctx) -> Session:
    return Session(ctx)
