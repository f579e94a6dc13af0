"""The hop encoder's dropout: ``flax.linen.Dropout``'s values from a mask
that the compiled step draws once and keeps (models/hop.py
``KeptMaskDropout``; PERF.md §6, PR 26).

Two halves.  On the CPU at a small size, against ``nn.Dropout`` itself
under the same key: loss and every gradient leaf bit for bit, the paths
without dropout the same program as before, the parameter tree unchanged.
Compiled for a described ``v5e:2x2`` (nothing runs, no chip needed): the
step holds threefry's rounds over ``[batch, hidden]`` in one fusion and in
none beside a matmul — the guard that a later jax or a later edit does not
put the six draws back.

The topology is described inside a fixture, never at import: only one
process may hold the TPU's library, and this is the one file of ``tests/``
that loads it.
"""

import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dragonfly2_tpu.models import hop
from dragonfly2_tpu.models.gnn import NeighborTable
from dragonfly2_tpu.models.hop import HopConfig, HopRanker

os.environ.setdefault("TPU_LOG_DIR", "disabled")

N, K, FEAT, B = 40, 4, 10, 64
PARAM_LEAVES = sorted(
    [f"HopEncoder_0/Dense_{i}/{leaf}" for i in range(3) for leaf in ("bias", "kernel")]
    + [f"Dense_{i}/{leaf}" for i in range(3) for leaf in ("bias", "kernel")]
    + ["HopEncoder_0/Embed_0/embedding"]
)


def _flax_dropout(monkeypatch):
    """The parent's layer in the encoder's seat: ``nn.Dropout`` under the
    name, and so the key, that the encoder gives its own."""
    monkeypatch.setattr(
        hop, "KeptMaskDropout",
        lambda rate, name: nn.Dropout(rate, deterministic=False, name=name),
    )


def _inputs():
    rng = np.random.default_rng(7)
    feats = jnp.asarray(rng.normal(size=(N, FEAT)), jnp.float32)
    table = NeighborTable(
        jnp.zeros((N, K), jnp.int32), jnp.ones((N, K), jnp.float32),
        jnp.zeros((N, K, 1), jnp.float32),
    )
    src = jnp.asarray(rng.integers(0, N, B), jnp.int32)
    dst = jnp.asarray(rng.integers(0, N, B), jnp.int32)
    target = jnp.asarray(rng.normal(size=(B,)), jnp.float32)
    return feats, table, src, dst, target


def _leaves(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(k.key for k in path): np.asarray(v) for path, v in flat}


def _loss_and_grads(cfg: HopConfig, *, train: bool):
    feats, table, src, dst, target = _inputs()
    model = HopRanker(cfg)
    params = model.init(jax.random.PRNGKey(0), feats, table, src, dst)["params"]
    key = jax.random.fold_in(jax.random.PRNGKey(1), 3)

    def loss_fn(p):
        pred = model.apply(
            {"params": p}, feats, table, src, dst, train=train, rngs={"dropout": key}
        )
        return jnp.mean((pred - target) ** 2)

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    return {"loss": np.asarray(loss), **_leaves(grads)}, str(jax.make_jaxpr(loss_fn)(params))


@pytest.fixture(scope="module")
def trained_both_ways():
    cfg = HopConfig(hidden=32, dropout=0.1)
    with pytest.MonkeyPatch.context() as mp:
        _flax_dropout(mp)
        want, _ = _loss_and_grads(cfg, train=True)
    got, program = _loss_and_grads(cfg, train=True)
    return want, got, program


@pytest.mark.parametrize("leaf", ["loss"] + PARAM_LEAVES)
def test_loss_and_every_gradient_leaf_are_flax_dropouts_bit_for_bit(leaf, trained_both_ways):
    want, got, program = trained_both_ways
    # The new layer ran, not the patch.
    assert "optimization_barrier" in program and "random_bits" in program
    assert sorted(got) == sorted(["loss"] + PARAM_LEAVES)
    assert got[leaf].dtype == want[leaf].dtype
    assert got[leaf].tobytes() == want[leaf].tobytes()
    assert np.any(got[leaf] != 0)


def test_the_mask_drops_a_tenth_and_scales_the_rest():
    x = jnp.ones((256, 128), jnp.bfloat16)
    y = hop.KeptMaskDropout(0.1).apply({}, x, rngs={"dropout": jax.random.PRNGKey(5)})
    want = nn.Dropout(0.1, deterministic=False).apply({}, x, rngs={"dropout": jax.random.PRNGKey(5)})
    assert y.dtype == jnp.bfloat16 and np.array_equal(np.asarray(y), np.asarray(want))
    dropped = float(np.mean(np.asarray(y, np.float32) == 0))
    assert 0.08 < dropped < 0.12


@pytest.mark.parametrize(
    "cfg, train",
    [(HopConfig(hidden=32, dropout=0.1), False), (HopConfig(hidden=32, dropout=0.0), True)],
    ids=["train_false", "dropout_zero"],
)
def test_without_dropout_the_program_is_the_one_flax_dropout_gave(cfg, train, monkeypatch):
    got, program = _loss_and_grads(cfg, train=train)
    _flax_dropout(monkeypatch)
    want, flax_program = _loss_and_grads(cfg, train=train)
    assert program == flax_program
    assert "optimization_barrier" not in program and "random_bits" not in program
    assert all(got[k].tobytes() == want[k].tobytes() for k in want)


@pytest.mark.parametrize("dropout", [0.1, 0.0])
def test_parameter_tree_is_unchanged(dropout, monkeypatch):
    feats, table, src, dst, _ = _inputs()
    model = HopRanker(HopConfig(hidden=32, dropout=dropout))
    init = lambda: _leaves(model.init(jax.random.PRNGKey(0), feats, table, src, dst)["params"])
    got = init()
    assert sorted(got) == PARAM_LEAVES
    _flax_dropout(monkeypatch)
    want = init()
    assert all(got[k].tobytes() == want[k].tobytes() for k in want)
    # With train=True at init too: the layer has no variables of its own.
    trained = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        feats, table, src, dst, train=True,
    )
    assert list(trained) == ["params"] and sorted(_leaves(trained["params"])) == PARAM_LEAVES


# -- compiled for the chip --------------------------------------------------

CHIP_BATCH, CHIP_HIDDEN = 4096, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the description away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip_step_computations(topo) -> dict:
    """``_graph_train_step`` for ``HopConfig(hidden=1024)`` compiled for one
    described chip: the optimised program's computations, name → body."""
    from jax.sharding import SingleDeviceSharding

    from dragonfly2_tpu.trainer.train import (
        TrainConfig, TrainState, _graph_train_step, _make_optimizer,
    )

    one_chip = SingleDeviceSharding(topo.devices[0])
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    n, k, feat = 2048, 16, 62
    model = HopRanker(HopConfig(hidden=CHIP_HIDDEN))
    nf = spec((n, feat), jnp.float32)
    table = NeighborTable(
        spec((n, k), jnp.int32), spec((n, k), jnp.float32), spec((n, k, 1), jnp.float32)
    )
    ids = spec((CHIP_BATCH,), jnp.int32)

    def make_state():
        z = lambda s: jnp.zeros(s.shape, s.dtype)
        params = model.init(
            jax.random.PRNGKey(0), z(nf), jax.tree_util.tree_map(z, table),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        )["params"]
        return TrainState.create(
            apply_fn=model.apply, params=params, tx=_make_optimizer(TrainConfig(), 100),
            dropout_rng=jax.random.PRNGKey(1),
        )

    state = jax.tree_util.tree_map(
        lambda s: spec(s.shape, s.dtype), jax.eval_shape(make_state)
    )
    step = jax.jit(
        lambda s, f, t, a, b, y: _graph_train_step(s, f, t, a, b, y, None),
        donate_argnums=(0,),
    )
    text = step.lower(state, nf, table, ids, ids, spec((CHIP_BATCH,), jnp.float32)).compile().as_text()
    return dict(re.findall(r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\{\n(.*?)\n\}", text, flags=re.S | re.M))


# A threefry round over the mask's words; the key's fold_in runs the same
# rounds over scalars and is not what this file counts.
_WIDE_ROUND = re.compile(rf"= u32\[{CHIP_BATCH},{CHIP_HIDDEN}\]\S* shift-right-logical\(")


def _reach(computations: dict, name: str) -> set:
    """``name`` and every computation its body calls, nested fusions too."""
    seen, todo = set(), [name]
    while todo:
        at = todo.pop()
        if at in seen or at not in computations:
            continue
        seen.add(at)
        todo += re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", computations[at])
    return seen


def test_chip_step_draws_the_masks_in_one_fusion(chip_step_computations):
    drawing = [n for n, body in chip_step_computations.items() if _WIDE_ROUND.search(body)]
    assert len(drawing) == 1, drawing
    body = chip_step_computations[drawing[0]]
    assert "HopEncoder_0/Dropout_0/" in body
    # At least one whole draw (the two calls' draws share some of theirs).
    assert len(_WIDE_ROUND.findall(body)) >= 20


def test_chip_step_has_no_draw_beside_a_matmul(chip_step_computations):
    reached = {
        n: [chip_step_computations[c] for c in _reach(chip_step_computations, n)]
        for n in chip_step_computations if n.startswith("fused_computation")
    }
    matmuls = [n for n, bodies in reached.items() if any(" convolution(" in b for b in bodies)]
    assert len(matmuls) >= 6
    assert [n for n in matmuls if any(_WIDE_ROUND.search(b) for b in reached[n])] == []
