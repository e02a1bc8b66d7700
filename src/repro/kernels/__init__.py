"""Pallas TPU kernels:

  threshold_find   exact per-client k-th-magnitude thresholds at TRACED k
                   (16-ary bit-pattern bisection, 8 streamed sweeps)
  fused_merge      traced-k apply/merge megakernel: EF correction, Top-K
                   masking, the codec stage, overlap counts, OPWA mask, and
                   the weighted aggregate in ONE pass over each (updates,
                   residuals) block
  expert_gmm       grouped matmuls of the MoE layer's held experts
  flash_attention  blockwise causal attention

``threshold_find`` + ``fused_merge`` form the traced-k megakernel pipeline
behind the kernel route of ``fed.engine.compress_merge_leaf`` — the route
that serves the paper's bandwidth-adaptive per-client CRs. Each kernel has a
jit'd wrapper in ops.py, and all but ``expert_gmm`` a pure-jnp oracle in
ref.py; validated in interpret mode on CPU, targeted at TPU VMEM tiling
(8 x 128 lanes).
"""
