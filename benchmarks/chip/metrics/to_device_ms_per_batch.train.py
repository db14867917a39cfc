"""to_device_ms_per_batch.train: mean length of the program's
``pipeline/to_device`` spans inside the window (ms): the body of
``kge_model.batch_to_device``, the host-to-device copy of one batch."""


def read(ctx):
    spans = ctx["trace"].spans("pipeline/to_device")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
