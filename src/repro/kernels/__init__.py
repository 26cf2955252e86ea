"""Pallas TPU kernels for the paper's compute hot-spots.

  psum_matmul.py      blocked GEMM: active (VMEM-resident accumulator,
                      reduction-innermost grid) vs passive (HBM psum spill,
                      reduction-outermost) schedules + fused activation;
                      psum_grouped_matmul, its grouped (per-expert) form
  moe_ffn.py          a DeepSeek-V2-style FFN stack: router, sort, grouped
                      and shared-expert GEMMs, combine; spans per layer
  conv2d_psum.py      the paper's channel-partitioned conv loop nest on MXU
  conv_network.py     whole-network runner: chains conv2d_psum over a
                      planned repro.plan.graph.NetworkGraph (branches, adds)
  flash_attention.py  online-softmax attention (active accumulation for
                      attention partial sums)
  ops.py              jit wrappers; schedules from the repro.plan planner
  ref.py              pure-jnp oracles (tests assert allclose in interpret
                      mode across shape/dtype sweeps)
"""
