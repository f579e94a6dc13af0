"""Records the trainer enqueued less records the train step counted on the
device (``trainer_online_records_enqueued_total`` -
``trainer_online_records_trained_total``), read after the driver's drain
and ``release()``, which closes the trainer and with it the ledger.  0 in
a sound run; a step that drops rows reads their number exactly."""


def read(run):
    from dragonfly2_tpu.utils.metrics import default_registry

    enqueued = default_registry.get("trainer_online_records_enqueued_total")
    trained = default_registry.get("trainer_online_records_trained_total")
    if enqueued is None or trained is None or not enqueued.value():
        return None
    return float(enqueued.value() - trained.value())
