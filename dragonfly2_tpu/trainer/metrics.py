"""Trainer metrics (reference: trainer/metrics/metrics.go:33-50 —
training_total / training_failure_total, extended with the TPU loop's
observables)."""

from __future__ import annotations

from ..utils.metrics import default_registry as _reg

TRAINING_TOTAL = _reg.counter(
    "trainer_training_total", "Training runs", ["model", "result"]
)
TRAINING_RECORDS = _reg.counter(
    "trainer_training_records_total", "Records consumed by training", ["model"]
)
TRAINING_DURATION = _reg.histogram(
    "trainer_training_duration_seconds", "Wall time per training run",
    buckets=(1, 5, 15, 60, 300, 900, 3600),
)
MODELS_PUBLISHED = _reg.counter(
    "trainer_models_published_total", "Models pushed to the registry", ["model"]
)
# Online node-id lifecycle (trainer/online_graph.py WireIngestAdapter —
# the scheduler host-GC analog, reference scheduler/config/config.go:176-197).
ONLINE_NODES_EVICTED = _reg.counter(
    "trainer_online_nodes_evicted_total",
    "Dense node ids reclaimed by TTL eviction in the online ingest adapter",
)
ONLINE_NODES_RECYCLED = _reg.counter(
    "trainer_online_nodes_recycled_total",
    "Embedding/optimizer rows reset after node-id recycling",
)
ONLINE_OVERFLOW_EDGES = _reg.counter(
    "trainer_online_overflow_edges_total",
    "Edges dropped because the online node table was full",
)
# (``trainer_xla_compiles_total`` lives with its listener, utils/compile_cache.py.)
# The online trainer's ledger (trainer/online_graph.py): what run() has
# handed to the device against what the device has finished, the second
# counted on the device by the step itself (TrainState.rows).
ONLINE_RECORDS_ENQUEUED = _reg.counter(
    "trainer_online_records_enqueued_total",
    "Records in the dispatches the online trainer has enqueued",
)
ONLINE_RECORDS_TRAINED = _reg.counter(
    "trainer_online_records_trained_total",
    "Records the device has finished training on, as the train step counted them",
)
ONLINE_DISPATCHES_IN_FLIGHT = _reg.gauge(
    "trainer_online_dispatches_in_flight",
    "Dispatches enqueued and not yet seen finished on the device",
)
# An expert layer's routing as the train step counted it on the device
# (TrainState.aux), advanced when the ledger sees a dispatch finished:
# every token-slot routed, and those whose expert lives on this chip.
MOE_SLOTS_ROUTED = _reg.counter(
    "trainer_moe_slots_routed_total",
    "Token-slots the expert layers routed (tokens x experts per token x layers)",
)
MOE_SLOTS_HELD = _reg.counter(
    "trainer_moe_slots_held_total",
    "Token-slots routed to an expert this trainer holds, none dropped",
)
# Where the expert layers hold a selection bias, the step also counts the
# slots routed to every expert, held here or not; set with the two above.
MOE_ROUTE_MAX_OVER_MEAN = _reg.gauge(
    "trainer_moe_route_max_over_mean",
    "Slots routed to the busiest expert of the busiest layer over the mean, all experts, last finished dispatch",
)
# The stream ranker's attention layers, by layer kind ("window", "full"),
# counted in the step and advanced with the slots above.
ATTN_KEYS_ATTENDED = _reg.counter(
    "trainer_attn_keys_attended_total",
    "Keys the attention layers' queries attended: in the query's segment, causal, in its window",
    label_names=("kind",),
)
ATTN_KEYS_IN_BAND = _reg.counter(
    "trainer_attn_keys_in_band_total",
    "Keys the attention layers' bands hold for their queries by position alone (causal, window)",
    label_names=("kind",),
)
