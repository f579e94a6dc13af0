"""Pallas TPU kernel: fused slot-row gather + mask-folded MLP scoring.

The columnar host store (scheduler/featcache.py, DESIGN.md §18) keys
serving state by SLOT ID — so the scorer no longer needs host-side
feature-matrix assembly at all.  This kernel takes the slot matrix, the
candidate/child slot-id vectors, and the per-edge feature block, and
produces scores in ONE device dispatch per batcher flush:

- **gather in kernel** — per candidate block, the parent and child rows
  are DMA'd out of the HBM-resident slot matrix by slot id (scalar
  prefetch + ``pltpu.make_async_copy``, the embedding-lookup pattern;
  precedent: ``ops/pallas_segment.py`` prefetches its block index the
  same way).  No ``[n, 2H+E]`` feature matrix ever exists — the concat
  is algebraically folded away:
- **split first layer** — ``x @ W0`` over the concatenated layout
  ``[child | parent | edge]`` is exactly
  ``child @ W0c + parent @ W0p + edge @ W0e`` with W0 row-partitioned,
  so the kernel runs three small MXU matmuls into one accumulator and
  never materializes x;
- **mask folded** — post-hoc feature masking is zeroed W0 rows (the PR-3
  bit-identity argument, trainer/export.py ``_serving_weights``), folded
  host-side once at scorer construction;
- **gelu chain in VMEM** — the remaining dense stack (the exported
  serving MLP is 32→64→64→1) runs on the block without leaving VMEM.

``FusedMLPScorer`` wraps the kernel behind the ``EdgeScorer`` surface
with ``static_shapes = True`` so ``ScorerBatcher`` pads flushes up its
bucket ladder — TPU serving is one dispatch per flush, no recompiles on
the steady state.  It keeps a device mirror of the slot matrix, synced
against the store's ``_row_version`` (one locked snapshot per stale
flush).  A pure-jnp fallback (``use_pallas=False``, the default off-TPU)
runs the same split-matmul algebra as one jit — CPU serving and the
ordering-equivalence tests use it; interpret mode exercises the real
kernel on CPU.

``rule_weighted_sum`` is the rule path's arm of the same story: the
evaluator's 6 pre-scaled component columns reduce to one ``[n, 6] @
[6, 1]`` matvec, provided as a (trivial) pallas kernel + jit wrapper for
TPU-serving parity.

Scores are float32 device math: orderings are property-tested equal to
the numpy reference scorer (tests/test_ops.py, test_sched_vectorized),
score values agree to float tolerance (sum order differs across the
three partial matmuls — same envelope as any XLA vs numpy reduction).
"""

from __future__ import annotations

import functools
import threading
from typing import TYPE_CHECKING, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..records.features import EDGE_FEATURE_DIM, HOST_FEATURE_DIM

if TYPE_CHECKING:  # lock-graph resolver type (§16): store lock nests
    from ..scheduler.featcache import HostFeatureCache

# The exported serving MLP depth the kernel hand-unrolls (32→64→64→1);
# other depths run the jnp fallback.
_KERNEL_LAYERS = 3

# A VMEM row is 128 lanes wide and Mosaic moves whole rows: the DMA of a
# 12-wide slot row is refused ("Slice shape along dimension 1 must be
# aligned to tiling (128), but is 12").  On the kernel path the slot
# matrix mirror and the two host blocks of W0 are therefore zero-padded
# along the host-feature axis to a lane multiple — the padded columns
# meet zero weight rows, so the scores are unchanged.
_LANES = 128


def _pad_host_axis(a: np.ndarray, axis: int) -> np.ndarray:
    pad = -a.shape[axis] % _LANES
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return np.pad(a, widths)

# Rule-evaluator component weights in evaluator.evaluate term order:
# piece, upload-success, free-upload, host-type, idc, location.
RULE_COMPONENT_WEIGHTS = (0.2, 0.2, 0.15, 0.15, 0.15, 0.15)


def _gelu(x):
    """gelu (tanh approx) — the scorer's exact serving formula
    (trainer/export._np_gelu): x*x*x, never x**3."""
    x3 = x * x * x
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608 * (x + 0.044715 * x3)))


def fold_post_hoc_weights(
    weights: List[Tuple[np.ndarray, np.ndarray]],
) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Zero the post-hoc feature ROWS of W0 (bit-identical to zeroing
    the feature columns — both make the dot terms exact 0.0)."""
    from ..records.features import POST_HOC_FEATURE_IDX

    w0, b0 = weights[0]
    w0 = np.array(w0, dtype=np.float32, copy=True)
    w0[list(POST_HOC_FEATURE_IDX), :] = 0.0
    return [(w0, np.asarray(b0, np.float32))] + [
        (np.asarray(w, np.float32), np.asarray(b, np.float32))
        for w, b in weights[1:]
    ]


def split_first_layer(
    w0: np.ndarray, host_dim: int = HOST_FEATURE_DIM
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Row-partition W0 over the ``[child | parent | edge]`` feature
    layout: (W0c [H, D1], W0p [H, D1], W0e [E, D1])."""
    return (
        np.ascontiguousarray(w0[:host_dim]),
        np.ascontiguousarray(w0[host_dim : 2 * host_dim]),
        np.ascontiguousarray(w0[2 * host_dim :]),
    )


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------


def _fused_score_kernel(
    slots_ref,    # scalar prefetch [n_pad] int32 — parent slot per row
    dslots_ref,   # scalar prefetch [n_pad] int32 — child slot per row
    mat_ref,      # [S, Hp] f32, HBM (ANY) — the lane-padded slot matrix mirror
    edge_ref,     # [CB, E] f32
    w0c_ref, w0p_ref, w0e_ref, b0_ref,   # first layer, row-partitioned
    w1_ref, b1_ref, w2_ref, b2_ref,      # gelu stack + scalar head
    out_ref,      # [CB, 1] f32
    prow_vmem,    # scratch [CB, Hp]
    crow_vmem,    # scratch [CB, Hp]
    sem,          # DMA semaphore
    *,
    cand_block: int,
):
    i = pl.program_id(0)
    base = i * cand_block

    def gather(j, _):
        s = slots_ref[base + j]
        d = dslots_ref[base + j]
        cp = pltpu.make_async_copy(
            mat_ref.at[pl.ds(s, 1), :], prow_vmem.at[pl.ds(j, 1), :], sem
        )
        cp.start()
        cp.wait()
        cp = pltpu.make_async_copy(
            mat_ref.at[pl.ds(d, 1), :], crow_vmem.at[pl.ds(j, 1), :], sem
        )
        cp.start()
        cp.wait()
        return 0

    jax.lax.fori_loop(0, cand_block, gather, 0)
    # First layer as three partial matmuls — the concat never exists.
    x = (
        jnp.dot(crow_vmem[:], w0c_ref[:], preferred_element_type=jnp.float32)
        + jnp.dot(prow_vmem[:], w0p_ref[:], preferred_element_type=jnp.float32)
        + jnp.dot(edge_ref[:], w0e_ref[:], preferred_element_type=jnp.float32)
        + b0_ref[:]
    )
    x = _gelu(x)
    x = jnp.dot(x, w1_ref[:], preferred_element_type=jnp.float32) + b1_ref[:]
    x = _gelu(x)
    out_ref[:] = (
        jnp.dot(x, w2_ref[:], preferred_element_type=jnp.float32) + b2_ref[:]
    )


def _fused_score_call(
    matrix, slots, dslots, edge, parts, *, cand_block: int, use_pallas: bool,
    interpret: bool,
):
    """One traced dispatch: gather + score.  ``parts`` is the weight
    pytree [(w0c, w0p, w0e, b0), (w1, b1), ..., (wk, bk)].
    ``use_pallas`` is partial-bound static and only ever True for the
    ``_KERNEL_LAYERS`` depth (decided at scorer construction); on that
    path ``matrix`` and the host blocks of W0 arrive lane-padded
    (``_pad_host_axis``)."""
    n_pad = edge.shape[0]
    if not use_pallas:
        # Split-matmul jnp fallback — identical algebra, XLA-fused
        # gather, arbitrary depth.
        w0c, w0p, w0e, b0 = parts[0]
        x = (
            jnp.take(matrix, dslots, axis=0) @ w0c
            + jnp.take(matrix, slots, axis=0) @ w0p
            + edge @ w0e
            + b0
        )
        for w, b in parts[1:]:
            x = _gelu(x)
            x = x @ w + b
        return x[:, 0]
    w0c, w0p, w0e, b0 = parts[0]
    w1, b1 = parts[1]
    w2, b2 = parts[2]
    hp = matrix.shape[1]
    d1 = w0c.shape[1]
    d2 = w1.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_pad // cand_block,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # slot matrix stays in HBM
            pl.BlockSpec((cand_block, EDGE_FEATURE_DIM), lambda i, s, d: (i, 0)),
            pl.BlockSpec((hp, d1), lambda i, s, d: (0, 0)),
            pl.BlockSpec((hp, d1), lambda i, s, d: (0, 0)),
            pl.BlockSpec((EDGE_FEATURE_DIM, d1), lambda i, s, d: (0, 0)),
            pl.BlockSpec((1, d1), lambda i, s, d: (0, 0)),
            pl.BlockSpec((d1, d2), lambda i, s, d: (0, 0)),
            pl.BlockSpec((1, d2), lambda i, s, d: (0, 0)),
            pl.BlockSpec((d2, 1), lambda i, s, d: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, s, d: (0, 0)),
        ],
        out_specs=pl.BlockSpec((cand_block, 1), lambda i, s, d: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((cand_block, hp), jnp.float32),
            pltpu.VMEM((cand_block, hp), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
    )
    kernel = functools.partial(_fused_score_kernel, cand_block=cand_block)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        interpret=interpret,
    )(
        slots, dslots, matrix, edge,
        w0c, w0p, w0e, b0, w1, b1, w2, b2,
    )
    return out[:, 0]


# ---------------------------------------------------------------------------
# EdgeScorer wrapper: the serving form
# ---------------------------------------------------------------------------


class FusedMLPScorer:
    """EdgeScorer over slot ids (scheduler/evaluator.py ``wants_slots``
    protocol): ``score(edge_block, src_buckets=parent_slots,
    dst_buckets=child_slots)`` — the host rows come out of the kernel's
    device mirror of the columnar store's slot matrix.

    ``static_shapes = True`` engages the batcher's pad ladder; this
    class additionally pads to its candidate-block multiple, so the
    device sees a handful of static shapes.  The mirror re-uploads only
    when the store's row version moved (one locked snapshot per stale
    flush — on TPU this piggybacks the dispatch; on CPU jit it is a
    zero-copy asarray).

    Standardized artifacts (``feat_mean`` set) are not supported — the
    post-hoc mask cannot fold into W1 there (trainer/export.py), so the
    fused first-layer split would not be mask-correct.
    """

    static_shapes = True
    wants_features = True
    wants_slots = True

    def __init__(
        self,
        store: "HostFeatureCache",
        weights: List[Tuple[np.ndarray, np.ndarray]],
        *,
        post_hoc_masked: bool = True,
        cand_block: int = 128,
        use_pallas: Optional[bool] = None,
        interpret: bool = False,
    ) -> None:
        from ..trainer.export import MLPScorer

        self._store = store
        self.cand_block = int(cand_block)
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        served = (
            fold_post_hoc_weights(weights) if post_hoc_masked
            else [
                (np.asarray(w, np.float32), np.asarray(b, np.float32))
                for w, b in weights
            ]
        )
        # Decided HERE so the traced body never branches on the weight
        # pytree: the kernel hand-unrolls exactly the exported serving
        # depth; other depths take the split-matmul jnp path.
        self._use_pallas = bool(use_pallas) and len(served) == _KERNEL_LAYERS
        w0c, w0p, w0e = split_first_layer(served[0][0])
        if self._use_pallas:
            w0c, w0p = _pad_host_axis(w0c, 0), _pad_host_axis(w0p, 0)
        parts = [(jnp.asarray(w0c), jnp.asarray(w0p), jnp.asarray(w0e),
                  jnp.asarray(served[0][1].reshape(1, -1)))]
        for w, b in served[1:]:
            parts.append((jnp.asarray(w), jnp.asarray(b.reshape(1, -1))))
        self._parts = parts
        # Reference path: the numpy serving scorer over assembled rows —
        # byte-identical to the non-fused serving path; used when the
        # store served uncached (no slots) or a shadow engine needs the
        # full feature matrix (scheduler/evaluator.py).
        self._ref = MLPScorer(weights=weights, post_hoc_masked=post_hoc_masked)
        # ONE cached trace per scorer (DF010): statics bound via partial.
        self._score_jit = jax.jit(
            functools.partial(
                _fused_score_call,
                cand_block=self.cand_block,
                use_pallas=self._use_pallas,
                interpret=bool(interpret),
            )
        )
        self._mirror_mu = threading.Lock()
        self._mat_dev = None
        self._mat_version = None

    @classmethod
    def from_scorer(cls, store, scorer, **kw) -> "FusedMLPScorer":
        """Build from an exported ``MLPScorer`` artifact."""
        if scorer.feat_mean is not None:
            raise ValueError(
                "standardized artifacts cannot serve fused: the post-hoc "
                "mask does not fold through (x-mean)/std (export.py)"
            )
        return cls(
            store, scorer.weights, post_hoc_masked=scorer.post_hoc_masked, **kw
        )

    def _sync_mirror(self):
        ver = self._store._row_version
        if ver == self._mat_version:
            return self._mat_dev
        with self._mirror_mu:
            if self._store._row_version != self._mat_version:
                version, snap = self._store.matrix_snapshot()
                if self._use_pallas:
                    snap = _pad_host_axis(snap, 1)
                self._mat_dev = jnp.asarray(snap)
                self._mat_version = version
            return self._mat_dev

    def score(self, features, *, src_buckets=None, dst_buckets=None) -> np.ndarray:  # dflint: hotpath
        """[n, EDGE_FEATURE_DIM] edge block + parent/child SLOT ids →
        [n] scores, one device dispatch (row-independent: padded rows
        and co-batched strangers cannot bleed — the batched-score
        contract)."""
        if src_buckets is None or dst_buckets is None:
            raise ValueError("FusedMLPScorer needs parent/child slot ids")
        edge = np.asarray(features, dtype=np.float32)
        n = edge.shape[0]
        cb = self.cand_block
        n_pad = -(-n // cb) * cb
        mat = self._sync_mirror()
        if n_pad != n:
            e = np.zeros((n_pad, edge.shape[1]), dtype=np.float32)
            e[:n] = edge
            s = np.zeros(n_pad, dtype=np.int32)
            s[:n] = src_buckets
            d = np.zeros(n_pad, dtype=np.int32)
            d[:n] = dst_buckets
        else:
            e = edge
            s = np.asarray(src_buckets, dtype=np.int32)
            d = np.asarray(dst_buckets, dtype=np.int32)
        out = self._score_jit(
            mat, jnp.asarray(s), jnp.asarray(d), jnp.asarray(e), self._parts
        )
        return np.asarray(out)[:n]

    def score_rows(self, features, **buckets) -> np.ndarray:
        """Assembled-row fallback: byte-identical to the plain numpy
        serving scorer."""
        return self._ref.score(features, **buckets)


# ---------------------------------------------------------------------------
# Rule arm: the weighted sum as one matvec
# ---------------------------------------------------------------------------


def _rule_sum_kernel(comp_ref, w_ref, out_ref):
    out_ref[:] = jnp.dot(
        comp_ref[:], w_ref[:], preferred_element_type=jnp.float32
    )


def _rule_sum_call(components, weights, *, use_pallas: bool, interpret: bool):
    if not use_pallas:
        return (components @ weights)[:, 0]
    n = components.shape[0]
    k = components.shape[1]
    out = pl.pallas_call(
        _rule_sum_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((n, k), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )(components, weights)
    return out[:, 0]


# Canonical cached traces (DF010: construct once at module scope, never
# per call) — one per execution mode.
_rule_sum_jit = jax.jit(
    functools.partial(_rule_sum_call, use_pallas=False, interpret=False)
)
_rule_sum_pallas_jit = jax.jit(
    functools.partial(_rule_sum_call, use_pallas=True, interpret=False)
)
_rule_sum_interpret_jit = jax.jit(
    functools.partial(_rule_sum_call, use_pallas=True, interpret=True)
)


def rule_weighted_sum(  # dflint: hotpath
    components: np.ndarray,
    weights=RULE_COMPONENT_WEIGHTS,
    *,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> np.ndarray:
    """[n, 6] rule component matrix → [n] scores on device: the rule
    path's arm of the fused dispatch (component columns gather off the
    columnar store; the weighted sum is one MXU matvec).  Pads rows to a
    lane multiple so the jit sees a bucket ladder of shapes."""
    comp = np.asarray(components, dtype=np.float32)
    n, k = comp.shape
    n_pad = max(-(-n // 128) * 128, 128)
    if n_pad != n:
        c = np.zeros((n_pad, k), dtype=np.float32)
        c[:n] = comp
    else:
        c = comp
    w = np.asarray(weights, dtype=np.float32).reshape(-1, 1)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if interpret:
        fn = _rule_sum_interpret_jit
    elif use_pallas:
        fn = _rule_sum_pallas_jit
    else:
        fn = _rule_sum_jit
    return np.asarray(fn(jnp.asarray(c), jnp.asarray(w)))[:n]
