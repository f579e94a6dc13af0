"""Subprocess body for the trainer-crash chaos drill (tests/test_chaos.py).

Runs a small OnlineGraphTrainer over a DETERMINISTIC record stream in
per-dispatch blocks, checkpointing every dispatch.  Modes:

- ``fresh``   start from scratch and train ``total`` dispatches.  With a
  crash FaultSpec on the ``trainer.dispatch`` seam (via DF_FAULTINJECT),
  the process SIGKILLs itself at an exact dispatch index — the
  deterministic "trainer dies mid-online-ingest" event.
- ``resume``  orbax-restore from the checkpoint, SKIP the stream prefix
  the restored ``records_seen`` says was already trained (exactly-once:
  re-feeding it would duplicate records; skipping more would lose them),
  and finish the remaining dispatches.

Prints ONE JSON line: {"state_hash", "records_seen", "dispatch"} — the
parent test compares it against an uninterrupted reference run.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

# Tests run on the virtual CPU mesh (tests/conftest.py).
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from dragonfly2_tpu.utils import faultinject  # noqa: E402

N_NODES = 64
FEAT_DIM = 8
BATCH = 64
SUPER_STEPS = 2
PER_DISPATCH = SUPER_STEPS * BATCH


def build(ckpt_dir):
    from dragonfly2_tpu.trainer.online_graph import (
        OnlineGraphConfig,
        OnlineGraphTrainer,
    )

    rng = np.random.default_rng(0)
    node_feats = rng.normal(size=(N_NODES, FEAT_DIM)).astype(np.float32)
    src = rng.integers(0, N_NODES, 256).astype(np.int32)
    dst = (src + 1 + rng.integers(0, N_NODES - 1, 256).astype(np.int32)) % N_NODES
    rtt = rng.uniform(1e-3, 1e-1, 256).astype(np.float32)
    cfg = OnlineGraphConfig(
        num_nodes=N_NODES, max_neighbors=4, batch_size=BATCH,
        super_steps=SUPER_STEPS, refresh_every=0, checkpoint_every=1,
        native_ingest=False, total_steps_hint=100,
    )
    trainer = OnlineGraphTrainer(
        cfg, node_feats=node_feats, topo_src=src, topo_dst=dst, topo_rtt=rtt,
        checkpoint_dir=ckpt_dir,
    )
    return trainer, cfg


def stream_blocks(total):
    """The record stream: one seeded generator, one block per dispatch —
    byte-identical across processes and runs."""
    rng = np.random.default_rng(42)
    for _ in range(total):
        src = rng.integers(0, N_NODES, PER_DISPATCH).astype(np.int32)
        dst = (
            src + 1 + rng.integers(0, N_NODES - 1, PER_DISPATCH).astype(np.int32)
        ) % N_NODES
        y = rng.uniform(0.0, 1.0, PER_DISPATCH).astype(np.float32)
        yield src, dst, y


def run(mode, ckpt_dir, total):
    from dragonfly2_tpu.trainer.online_graph import state_hash

    trainer, _cfg = build(ckpt_dir)
    start = 0
    if mode == "resume":
        assert trainer.resume(), "resume found no checkpoint"
        assert trainer.records_seen % PER_DISPATCH == 0, trainer.records_seen
        start = trainer.records_seen // PER_DISPATCH
        print(f"chaos-child: resumed at dispatch {start}", flush=True)
    for i, (src, dst, y) in enumerate(stream_blocks(total)):
        if i < start:
            continue  # trained before the crash — re-feeding = duplicates
        trainer.feed_downloads(src, dst, y)
        trainer.run(max_dispatches=1, idle_timeout=10.0)
    return {
        "state_hash": state_hash(trainer.state),
        "records_seen": trainer.records_seen,
        "dispatch": trainer.dispatch,
    }


def main():
    faultinject.install_from_env()
    mode, ckpt_dir, total = sys.argv[1], sys.argv[2], int(sys.argv[3])
    print("chaos-child: ready", flush=True)
    print(json.dumps(run(mode, ckpt_dir, total)), flush=True)


if __name__ == "__main__":
    main()
