"""Pallas TPU kernels for the compute hot-spots, compiled on an accelerator
and interpreted on the CPU backend (``backend.pallas_interpret``):

  flash_attention  tiled online-softmax causal/full GQA attention (prefill)
  wkv6             chunked RWKV6 linear-attention recurrence
  sweep_burn       MXU-aligned sustained-matmul probe (the §5.2 offline
                   sweep's compute workload)
  fleet_score      fused peer-median/MAD/robust-z/threshold scorer over
                   the detector's circular (depth, N) buffers (§4.2)
"""
