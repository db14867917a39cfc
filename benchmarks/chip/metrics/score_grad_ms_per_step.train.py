"""score_grad_ms_per_step.train: device time of the operations under the
program's ``kge.score_grad`` scope (gather, score, loss and backward), per
step of the window (ms). An operation is under the scope when its name or
its stats (the op name the compiler keeps) contain it."""

SCOPE = ("kge.score_grad",)


def read(ctx):
    seconds = ctx["trace"].kernel_seconds(SCOPE)
    if not ctx.get("steps") or seconds <= 0:
        return None
    return 1e3 * seconds / ctx["steps"]
