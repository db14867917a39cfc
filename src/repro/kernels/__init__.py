"""Pallas TPU kernels for the compute hot-spots.

kge_score       — the paper's T1 hot loop: joint-negative pairwise scores
                  (dot / squared-L2 / L1) as MXU-tiled GEMM-form kernels.
                  (Paper §3.3: "converted into a generalized matrix
                  multiplication, performed using highly optimized math
                  libraries" — here, the MXU via Pallas.) Also TransR's
                  projected negative distances (``kge.transr_score``).
sparse_adagrad  — the fused in-place sparse-Adagrad row update on whole
                  8-row tiles; its wrapper sums the gradients of duplicate rows.

Each kernel package ships <name>.py (pl.pallas_call + BlockSpec), ops.py
(jit'd wrapper + custom_vjp), ref.py (pure-jnp oracle). All validated in
interpret mode on CPU; BlockSpecs are sized for TPU v5e VMEM/MXU.
"""
