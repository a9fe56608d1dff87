"""Tensor-relational ops for model code.

Each op's *forward* is an FRA query lowered by the chunked compiler, and
its *backward* is the gradient query generated by relational auto-diff
(core/autodiff.py) — wrapped in ``jax.custom_vjp`` so that model code and
optimizers built on ``jax.grad`` transparently execute the paper's
RJP-generated plans instead of JAX's own AD through the engine.
"""

from .linear import rel_linear, rel_matmul  # noqa: F401
from .gcn import gcn_conv, gcn_square_loss, partitioned_edges  # noqa: F401
from .embedding import rel_embed  # noqa: F401
