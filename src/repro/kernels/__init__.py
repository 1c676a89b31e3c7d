"""Pallas TPU kernels for the paper's compute hot-spots.

- fwht:          fused ROS preconditioning y = H(d⊙x) — Kronecker MXU form
- sketch_fused:  the FULL compression operator (precondition → sample) in one
                 VMEM round trip — the streaming-ingest fast path
- sparse_assign: Alg. 1's passes over a retained sketch (K-means++ candidate
                 distances, assignment argmin/min, Eq. 39 sums and counts)
- spmm:          sparse-times-dense pair (W·Omega and Wᵀ·T) feeding the
                 low-rank spectral accumulators without densifying the batch;
                 p-tiled so the VMEM footprint is bounded at any p
- ops:           public wrappers (backend auto-selection)
- ref:           pure-jnp oracles used for validation
"""
from repro.kernels import fwht, ops, ref, sketch_fused, sparse_assign, spmm  # noqa: F401
