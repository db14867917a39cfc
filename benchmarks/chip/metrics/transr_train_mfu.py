"""transr_train_mfu: TransR's score-function FLOPs forward and backward
(``counts_proj.train_step_flops``) over the window's steps, per second, as a
share of the chip's bf16 peak (%)."""

import counts_proj


def read(ctx):
    k = ctx["kge"]
    if not ctx.get("steps") or k["model"] != "transr":
        return None
    flops = counts_proj.train_step_flops(
        k["batch_size"], k["neg_sample_size"], k["batch_size"] // k["neg_group_size"],
        k["dim"], k["rel_dim"] or k["dim"])
    return 100.0 * flops * ctx["steps"] / ctx["seconds"] / ctx["peaks"]["bf16_flops"]
