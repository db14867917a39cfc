"""data_wait_ms_per_step.train: the program's ``pipeline/consumer_wait_s``
counter (the step loop waiting for a batch) over the window, per step (ms)."""


def read(ctx):
    trace = ctx["trace"]
    if not ctx.get("steps") or not trace.counter_delta("pipeline/produced"):
        return None
    return 1e3 * trace.counter_delta("pipeline/consumer_wait_s") / ctx["steps"]
