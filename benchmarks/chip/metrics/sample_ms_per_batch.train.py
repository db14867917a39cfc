"""sample_ms_per_batch.train: mean length of the program's ``pipeline/sample``
spans (one per batch the host sampler makes) inside the window (ms)."""


def read(ctx):
    spans = ctx["trace"].spans("pipeline/sample")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
