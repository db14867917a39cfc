"""sampler_ms_per_batch.train: mean length of the program's ``sampler/sample``
spans inside the window (ms): the body of ``JointSampler.sample``, numpy
sampling only, one span per batch."""


def read(ctx):
    spans = ctx["trace"].spans("sampler/sample")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
