"""DF012 columnar dtype/shape contract registry — declared ONCE, checked twice.

Every columnar surface the TPU loop depends on (DFC1 record files, the
HostFeatureCache slot matrix, scorer blob arrays, the pallas kernel
outputs) declares its dtype contract here, in one literal dict:

- **statically**, ``tools/dflint/tracerules.py`` (rule DF012) parses this
  file's AST (``ast.literal_eval`` — no import, dflint stays stdlib-only)
  and checks every producer/consumer seam named below: creation-site
  dtype pins for slot columns, constructor/param defaults, float64 leaks
  (x64 is off — a float64 request silently truncates under jit, and on
  host it doubles DFC1 row width), and implicit-float64 array
  constructors (``np.zeros(n)`` defaults to float64).
- **dynamically**, tests import this module and assert the live objects
  agree: ``records.features.DOWNLOAD_COLUMNS`` must equal the declared
  column list, kernel outputs must come back in the declared dtype for
  empty/single/bf16 inputs (tests/test_ops.py), so kernel and contract
  cannot drift apart.

Because dflint evaluates ``CONTRACTS`` with ``ast.literal_eval``, the
dict MUST stay a pure literal: no names, calls, or comprehensions.

Entry shapes (all fields optional except the key):

- ``file``      — repo-relative path the entry's code lives in;
- ``columns``   — the declared column-name list (runtime-asserted);
- ``dtype``     — the contract dtype for produced arrays;
- ``allow``     — extra dtype names reviewed as legitimate in these
                  functions (documented widened intermediate math, e.g.
                  float64 accumulation that rounds once on assignment);
- ``functions`` — producer/consumer functions scanned for dtype leaks;
- ``attrs``     — ``"Class.attr" -> dtype`` creation-site pins;
- ``defaults``  — ``"Class.field"`` / ``"Class.fn.param"`` -> required
                  literal default.
"""

from __future__ import annotations

CONTRACTS = {
    # -- DFC1 download rows (records/features.py + records/columnar.py) ----
    "dfc1.download": {
        "file": "dragonfly2_tpu/records/features.py",
        "dtype": "float32",
        # STRICT: the reviewed float64 intermediates in
        # edge_features_batch carry inline `# dflint: disable=DF012`
        # pragmas instead of a blanket allow, so widening any OTHER
        # construction to float64 still fails by contract name.
        "functions": [
            "download_to_rows",
            "host_features",
            "edge_features",
            "edge_features_batch",
            "mask_post_hoc",
        ],
        "columns": [
            "src_bucket", "dst_bucket",
            "child_cpu_percent", "child_mem_used_percent",
            "child_disk_used_percent", "child_tcp_conn_log",
            "child_upload_tcp_conn_log", "child_upload_load",
            "child_upload_success_ratio", "child_upload_count_log",
            "child_type_normal", "child_type_super", "child_type_strong",
            "child_type_weak",
            "parent_cpu_percent", "parent_mem_used_percent",
            "parent_disk_used_percent", "parent_tcp_conn_log",
            "parent_upload_tcp_conn_log", "parent_upload_load",
            "parent_upload_success_ratio", "parent_upload_count_log",
            "parent_type_normal", "parent_type_super", "parent_type_strong",
            "parent_type_weak",
            "same_idc", "location_affinity", "piece_count_log",
            "mean_piece_size_log", "content_length_log",
            "finished_piece_ratio", "parent_cost_log_s",
            "parent_upload_pieces_log",
            "target_log_bw",
        ],
    },
    "dfc1.topology": {
        "file": "dragonfly2_tpu/records/features.py",
        "dtype": "float32",
        "functions": ["topology_to_rows"],
        "columns": [
            "src_bucket", "dst_bucket", "avg_rtt_norm", "src_tcp_conn_log",
            "dst_tcp_conn_log", "same_idc", "location_affinity", "freshness",
        ],
    },
    "dfc1.file": {
        "file": "dragonfly2_tpu/records/columnar.py",
        "dtype": "float32",
        "defaults": {
            "ColumnarHeader.dtype": "float32",
            "ColumnarWriter.__init__.dtype": "float32",
        },
    },
    # -- Columnar host store (scheduler/featcache.py, DESIGN.md §18) -------
    # The slot matrix is the SOURCE OF TRUTH for host serving state:
    # every column is creation-site pinned, so widening any of them (or
    # adding an unpinned float64 construction to a producer) fails lint
    # by contract name.  float64 is DELIBERATE for the timestamp and the
    # pre-scaled rule-score columns: they must reproduce the scalar
    # oracle's python-double math bit-for-bit (host code, never traced).
    "featcache.slots": {
        "file": "dragonfly2_tpu/scheduler/featcache.py",
        "dtype": "float32",
        "allow": ["float64"],
        "attrs": {
            "HostFeatureCache._matrix": "float32",
            "HostFeatureCache._bucket_col": "int64",
            "HostFeatureCache._idc_col": "int64",
            "HostFeatureCache._idc_ci_col": "int64",
            "HostFeatureCache._loc_col": "int64",
            "HostFeatureCache._upload_count_col": "int64",
            "HostFeatureCache._upload_failed_col": "int64",
            "HostFeatureCache._concurrent_upload_col": "int64",
            "HostFeatureCache._upload_limit_col": "int64",
            "HostFeatureCache._peer_count_col": "int64",
            "HostFeatureCache._updated_at_col": "float64",
            "HostFeatureCache._rule_w_cols": "float64",
            "HostFeatureCache._pair_col": "int64",
            "HostFeatureCache._type_normal_col": "int8",
            "HostFeatureCache._stamp_col": "int64",
        },
        "functions": [
            "HostFeatureCache.serve",
            "HostFeatureCache.rule_serve",
            "HostFeatureCache.rule_scores",
            "HostFeatureCache.gather_with_buckets",
            "HostFeatureCache._fill_slot_locked",
            "HostFeatureCache._derive_upload_cells",
            "HostFeatureCache.write_upload_state",
            "HostFeatureCache._serve_uncached",
            "HostFeatureCache._rule_serve_uncached",
            "HostFeatureCache._aff_row_locked",
            "HostFeatureCache._pair_row_locked",
        ],
    },
    # -- scorer blob arrays (trainer/export.py) ----------------------------
    "scorer.mlp": {
        "file": "dragonfly2_tpu/trainer/export.py",
        "dtype": "float32",
        # STRICT: feature_snapshot_stats' float64 binning carries inline
        # pragmas (rounds once on return) — see dfc1.download.
        "functions": [
            "_flatten_mlp_params",
            "export_mlp_scorer",
            "export_from_state",
            "feature_snapshot_stats",
            "_pack",
            "load_scorer",
            "MLPScorer.score",
            "MLPScorer._serving_weights",
        ],
    },
    # int8/bf16 post-training-quantized serving variant: the blob packs
    # quantized payloads + per-channel scales next to the drift
    # histograms; scoring runs the float32 DEQUANTIZED weights, so every
    # producer below must stay float32-out (int8/uint16 payloads are the
    # storage form, not a compute dtype).
    "scorer.quantized": {
        "file": "dragonfly2_tpu/trainer/export.py",
        "dtype": "float32",
        "functions": [
            "quantize_scorer",
            "_int8_quantize",
            "_bf16_round",
            "_dequantize_layers",
        ],
    },
    "scorer.gnn": {
        "file": "dragonfly2_tpu/trainer/export.py",
        "dtype": "float32",
        "functions": [
            "export_gnn_scorer",
            "gnn_scorer_to_bytes",
            "GNNScorer.score",
            "GNNScorer._lookup",
            "GNNScorer.__post_init__",
        ],
    },
    # -- TPU kernels (ops/) -------------------------------------------------
    "ops.segment_sum": {
        "file": "dragonfly2_tpu/ops/pallas_segment.py",
        "dtype": "float32",
        # exact=False runs native bf16 MXU passes with f32 accumulate.
        "allow": ["bfloat16"],
        "functions": [
            "bucket_edges_by_block",
            "_segment_kernel",
            "segment_sum_pallas",
            "_segment_sum_bucketed",
            "make_neighbor_gather",
        ],
    },
    # Fused slot-row gather + mask-folded MLP scoring kernel over the
    # columnar host store's slot matrix (DESIGN.md §18): everything is
    # float32 end to end (slot ids int32 are the storage/index form).
    "ops.fused_score": {
        "file": "dragonfly2_tpu/ops/pallas_score.py",
        "dtype": "float32",
        "functions": [
            "fold_post_hoc_weights",
            "split_first_layer",
            "_fused_score_kernel",
            "_fused_score_call",
            "FusedMLPScorer.score",
            "FusedMLPScorer.score_rows",
            "FusedMLPScorer._sync_mirror",
            "_rule_sum_kernel",
            "_rule_sum_call",
            "rule_weighted_sum",
        ],
    },
    # Indexed row copies of the stream ranker's expert layer (models/
    # stream.py::routed_experts): sums are float32; the rows gathered are
    # the model's compute dtype (bfloat16 on the chip, two columns a
    # uint32 word in the packed form), copied and never computed with.
    "ops.slot_rows": {
        "file": "dragonfly2_tpu/ops/slot_rows.py",
        "dtype": "float32",
        "allow": ["bfloat16"],
        "functions": [
            "pack",
            "unpack",
            "_pack_kernel",
            "_unpack_into",
            "_gather_kernel",
            "gather_packed",
            "gather_rows",
            "_add_kernel",
            "add_packed",
            "add_rows",
        ],
    },
}
