"""adagrad_update_ms_per_step.train: device time of the operations under the
program's ``kge.adagrad_update`` scope (the row update of every
sparse-Adagrad update, entity and relation tables), per step of the window
(ms). An operation is under the scope when its name or its stats (the op
name the compiler keeps) contain it."""

SCOPE = ("kge.adagrad_update",)


def read(ctx):
    seconds = ctx["trace"].kernel_seconds(SCOPE)
    if not ctx.get("steps") or seconds <= 0:
        return None
    return 1e3 * seconds / ctx["steps"]
