"""triton_dist_tpu: a TPU-native compute-communication overlapping framework.

A ground-up JAX/Pallas/Mosaic re-design of the capabilities of
Triton-distributed (reference: /root/reference): one-sided symmetric-memory
communication programmed directly inside tile kernels, so that
AllGather-GEMM, GEMM-ReduceScatter, fused GEMM-AllReduce, MoE
expert-parallel all2all, sequence-parallel attention and pipeline-parallel
P2P all hide communication behind compute.

Layer map (mirrors reference SURVEY.md section 1, re-targeted to TPU):
  L0  ICI remote-DMA + semaphores   (Pallas pltpu primitives; ref: shmem/)
  L2  language facade `dl.*`        (triton_dist_tpu.language; ref: python/triton_dist/language)
  L3  host runtime                  (triton_dist_tpu.runtime;  ref: python/triton_dist/utils.py)
  L4  overlapped kernel library     (triton_dist_tpu.kernels;  ref: python/triton_dist/kernels)
  L5  layers                        (triton_dist_tpu.layers;   ref: python/triton_dist/layers)
  L6  models + inference engine     (triton_dist_tpu.models;   ref: python/triton_dist/models)
  L8  tools                         (triton_dist_tpu.tools;    ref: python/triton_dist/tools)
"""

__version__ = "0.1.0"

from triton_dist_tpu.runtime.bootstrap import (  # noqa: F401
    initialize_distributed,
    finalize_distributed,
    get_context,
    DistContext,
)
from triton_dist_tpu.utils import dist_print  # noqa: F401
