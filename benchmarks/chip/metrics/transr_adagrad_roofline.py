"""transr_adagrad_roofline: the least time of the window's sparse Adagrad
updates of the entity, relation and projection tables, over the device time
of the program's ops named ``kge.adagrad_update`` (the update kernels, whose
custom calls a v5e trace names ``kge.adagrad_update.N``) (%). The least time
is the bytes any sparse update must move (unique rows per step, counted on
the host from the batches fed, x row bytes x 5; a projection row is touched
with its relation) over the chip's HBM bandwidth."""

import counts

KERNELS = ("kge.adagrad_update",)


def read(ctx):
    u = ctx["unique_rows"]
    kernel_s = ctx["trace"].kernel_seconds(KERNELS)
    if not ctx.get("steps") or u.get("proj") is None or kernel_s <= 0:
        return None
    k = ctx["kge"]
    rd = k["rel_dim"] or k["dim"]
    step_bytes = (counts.sparse_adagrad_bytes(u["entity"], k["dim"])
                  + counts.sparse_adagrad_bytes(u["rel"], rd)
                  + counts.sparse_adagrad_bytes(u["proj"], k["dim"] * rd))
    least_s = step_bytes * ctx["steps"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
