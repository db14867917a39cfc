from repro.kernels.kge_score.ops import kernel_pairwise_fn, pairwise_scores_kernel, transr_l2sq

__all__ = ["pairwise_scores_kernel", "kernel_pairwise_fn", "transr_l2sq"]
