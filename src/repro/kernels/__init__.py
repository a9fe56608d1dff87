"""Pallas TPU kernels for the engine's compute hot-spots.

The paper's hot path is the chunked join-aggregate (Σ⋈). On TPU this maps
to two kernels:

  matmul/   — MXU-tiled blocked matmul with VMEM accumulation: the Σ⋈ with
              ⊗ = MatMul over DenseRelations (paper Fig. 4 / Appendix A).
  segsum/   — segment-sum of edge messages: the Σ-by-dst over a CooRelation
              (GCN message passing). TPU-native adaptation: the scatter-add
              a GPU engine would use is re-expressed as one-hot × message
              matmuls so the reduction runs on the MXU instead of relying
              on random-access memory writes the TPU does not have.

Each kernel package has: <name>.py (pl.pallas_call + BlockSpec),
ops.py (jit'd public wrapper with an explicit ``interpret`` flag for the
Pallas interpreter, and a custom VJP so
reverse-mode AD differentiates through the Pallas forward), ref.py
(pure-jnp oracle used by tests and served as the ``ref`` dispatch tier).

matmul and segsum are wired into the engine through the kernel dispatch
registry in core/kernels.py: the chunked compiler resolves its
segment-sum and matmul-shaped join-aggregate lowerings against the
registry, which routes them here on TPU (and, when forced, to the
interpret/ref tiers on CPU). See docs/kernels.md for the registry
contract and the authoring walkthrough.
"""
