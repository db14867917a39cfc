"""transr_score_roofline: the least time of the window's projected scoring
(max of its FLOPs over the bf16 peak and its least bytes over the HBM
bandwidth, ``counts_proj``), over the device time of the program's kernels
named ``kge.transr_score`` (the forward, and ``kge.transr_score_bwd``) (%).
A v5e trace names each kernel's op after the kernel (``kge.transr_score.N``)."""

import counts_proj

KERNELS = ("kge.transr_score",)


def read(ctx):
    k = ctx["kge"]
    kernel_s = ctx["trace"].kernel_seconds(KERNELS)
    if not ctx.get("steps") or kernel_s <= 0:
        return None
    shape = (k["batch_size"], k["neg_sample_size"], k["batch_size"] // k["neg_group_size"],
             k["dim"], k["rel_dim"] or k["dim"])
    p = ctx["peaks"]
    least_s = max(counts_proj.projected_step_flops(*shape) / p["bf16_flops"],
                  counts_proj.projected_step_bytes(*shape) / p["hbm_bytes_per_s"])
    return 100.0 * least_s * ctx["steps"] / kernel_s
