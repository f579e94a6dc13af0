"""Graph aggregation ops — the GNN's hot path, XLA + pallas.

The reference has no tensor ops (its "aggregation" is Go loops over Redis
lists, scheduler/networktopology/probes.go).  Here neighbor aggregation is
the FLOPs-heavy core of the GNN trainer, with these implementations:

- ``aggregate``      — XLA reference ops: padded-table masked mean (one
  gather + reduce) and sorted-edge segment ops.  Always available; the
  numerics oracle for the kernel tests.
- ``pallas_segment`` — TPU pallas kernel computing edge→node segment-sum
  as a sequence of one-hot MXU matmuls over bucketed edge blocks (the
  TPU-native way to scatter-accumulate: the MXU does the reduction,
  no serialized scatter).
- ``pallas_score``   — the scheduler serving plane's fused slot-row
  gather + mask-folded MLP scoring kernel over the columnar host
  store's slot matrix (DESIGN.md §18), plus the rule path's
  weighted-sum matvec arm.
- ``slot_rows``      — the stream ranker's expert layer's indexed row
  copies (a block's rows gathered into expert order, the products' rows
  added back in place) as DMA kernels with many copies in flight;
  imported by ``models/stream.py`` as a module, ``jnp.take`` /
  ``.at[].add`` off the TPU.
- ``delta_scan``     — the stream ranker's Gated DeltaNet layers' loop
  over a row's chunks with the carried state held in VMEM, forward and
  backward, one Mosaic call a pass; imported by ``models/stream.py`` as
  a module, a ``lax.scan`` off the TPU.
- ``grouped_matmul`` — the stream ranker's expert blocks' grouped
  products (rows by their expert's weights, and the weights' gradients
  group by group), one Mosaic call a product; imported by
  ``models/stream.py`` as a module, ``jax.lax.ragged_dot`` off the TPU.
- ``attention_bwd``  — the stream ranker's blockwise segment attention's
  backward, a row's block pairs walked query block by query block with
  the row's ``dk`` and ``dv`` summed in VMEM, one Mosaic call a row and
  layer; imported by ``models/stream.py`` as a module, the loop over
  block pairs in XLA off the TPU.
- ``parallel.graph_sharding`` (sibling package) — shard_map-partitioned
  aggregation for graphs larger than one chip.
"""

from .aggregate import (  # noqa: F401
    masked_mean_aggregate,
    segment_mean,
    segment_sum,
)
from .pallas_segment import (  # noqa: F401
    bucket_edges_by_block,
    make_neighbor_gather,
    segment_sum_pallas,
)
from .pallas_score import (  # noqa: F401
    FusedMLPScorer,
    fold_post_hoc_weights,
    rule_weighted_sum,
    split_first_layer,
)
