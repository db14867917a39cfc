from repro.kernels.sparse_adagrad.ops import fused_sparse_adagrad

__all__ = ["fused_sparse_adagrad"]
