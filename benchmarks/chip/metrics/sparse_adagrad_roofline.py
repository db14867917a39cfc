"""sparse_adagrad_roofline: the least time the window's sparse Adagrad
updates need, over the device time of the program's sparse-Adagrad kernels
(%). The least time is the bytes any sparse update must move (unique rows
touched per step, counted on the host from the batches fed, x row bytes x 5)
over the chip's HBM bandwidth. The kernels are found in the trace by their
names, or by the ``tpu_custom_call`` target in the HLO text that a TPU trace
gives as the op's name (the compiled step names them ``_unknown_.N``), or by
the ``pallas_call`` op name in their stats: the train step has no other
Pallas call."""

import counts

KERNELS = ("_dedup_kernel", "_update_kernel", "tpu_custom_call", "pallas_call")


def read(ctx):
    u = ctx["unique_rows"]
    kernel_s = ctx["trace"].kernel_seconds(KERNELS)
    if not ctx.get("steps") or u["entity"] is None or kernel_s <= 0:
        return None
    k = ctx["kge"]
    step_bytes = (counts.sparse_adagrad_bytes(u["entity"], k["dim"])
                  + counts.sparse_adagrad_bytes(u["rel"], k["rel_dim"] or k["dim"]))
    least_s = step_bytes * ctx["steps"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
