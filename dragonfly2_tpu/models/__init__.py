"""Model zoo for the learned scheduling pipeline.

The reference defines exactly two model *types* in its registry —
``mlp`` and ``gnn`` (manager/models/model.go:35-46) — and never implements
either (trainer/training/training.go:82-99 is the stub).  Here:

- ``mlp``  — bandwidth regressor over download-record edge features
             (BASELINE configs[0]).
- ``gnn``  — GraphSAGE encoder over the probe graph (configs[1]) and a
             GAT parent ranker (configs[2]); both use static-shape padded
             neighbor tables so XLA compiles once.

- ``stream`` — transfer-stream ranker: a child's transfers in arrival
             order through a published decoder (Gated DeltaNet, gated,
             windowed, position-free or latent attention, leading dense
             layers, routed experts: the configuration lists each
             layer's kind), one expert-parallel share a chip;
             trained by the online trainer only.

All models compute in bfloat16 on the MXU with float32 params/reductions.
``build_ranker`` makes the online trainer's ranker from a configuration's
type.
"""

from .mlp import MLPRegressor, MLPConfig  # noqa: F401
from .gnn import (  # noqa: F401
    GATRanker,
    GNNConfig,
    GraphSAGE,
    NeighborTable,
    build_neighbor_table,
)
from .hop import (  # noqa: F401
    HopConfig,
    HopRanker,
    precompute_hop_features,
)
from .stream import (  # noqa: F401
    StreamRanker,
    StreamRankerConfig,
    carrier_attrs,
    fold_step_counts,
    previous_target,
)

import functools as _functools
from dataclasses import dataclass as _dataclass
from typing import Any as _Any, Callable as _Callable, Optional as _Optional


@_dataclass(frozen=True)
class Ranker:
    """What the online trainer needs of a ranker beside its module."""

    module: _Any
    # (dst, y) -> query edge features, built on the device inside the step
    # from the batch itself; None where the model takes none.
    query_feats: _Optional[_Callable] = None
    # (aux, span) of a dispatch the ledger has seen finished: what the
    # model's ``aux`` collection counted over it (host arrays) into the
    # model's own counters and span attributes; None where it sows nothing.
    fold: _Optional[_Callable] = None
    # () -> attributes for the ``trainer/run`` span: what the model knows
    # of how its step runs on this backend; None where it has nothing to say.
    run_attrs: _Optional[_Callable] = None
    batch_multiple: int = 1
    # Has a seat in trainer/export.py and keeps every per-node row under
    # the ``embedding`` key that id recycling resets.
    servable: bool = True


_RANKERS = {
    HopConfig: lambda c: Ranker(HopRanker(c)),
    StreamRankerConfig: lambda c: Ranker(
        StreamRanker(c),
        query_feats=_functools.partial(previous_target, positions=c.positions),
        fold=fold_step_counts,
        run_attrs=_functools.partial(carrier_attrs, c),
        batch_multiple=c.positions,
        servable=False,
    ),
}


def build_ranker(config) -> Ranker:
    """The ranker a model configuration describes, by the configuration's
    type."""
    make = _RANKERS.get(type(config))
    if make is None:
        raise TypeError(
            f"no ranker is built from a {type(config).__name__}; known: "
            f"{sorted(t.__name__ for t in _RANKERS)}"
        )
    return make(config)


def require_servable(config_or_module, where: str) -> None:
    """The one refusal of a ranker that trains online only: it has no
    exported scorer yet (ROADMAP Reach 8's seat) and no offline job."""
    config = getattr(config_or_module, "config", config_or_module)
    if type(config) in _RANKERS and not build_ranker(config).servable:
        raise NotImplementedError(
            f"{where}: a {type(config).__name__} trains through "
            f"OnlineGraphTrainer.run() only; it has no exported scorer, no "
            f"offline job and no id recycling until its head's per-host "
            f"columns are reset with the embedding (ROADMAP Reach 8, 10)"
        )
