"""train_mfu: the score function's forward and backward FLOPs over the
window's steps, per second, as a share of the chip's bf16 peak (%)."""

import counts


def read(ctx):
    if not ctx.get("steps"):
        return None
    k = ctx["kge"]
    flops = counts.train_step_flops(k["model"], k["batch_size"], k["neg_sample_size"],
                                    k["batch_size"] // k["neg_group_size"], k["dim"])
    return 100.0 * flops * ctx["steps"] / ctx["seconds"] / ctx["peaks"]["bf16_flops"]
