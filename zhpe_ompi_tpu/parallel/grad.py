"""Differentiation-aware collective wrappers (the Megatron f/g pair).

MPI has no AD story; a TPU-native framework must.  When a collective sits
inside a differentiated region, its transpose matters:

- :func:`g_allreduce` — allreduce in the forward, **identity** in the
  backward.  Correct when the allreduce produces a replicated value consumed
  identically by all ranks of the axis (tensor-parallel output projections).
- :func:`f_identity` — identity in the forward, **allreduce-sum** in the
  backward.  Correct at the *entry* of a rank-sharded parallel region whose
  input is replicated: each rank's backward contributes a partial input
  cotangent that must be summed.

Without these, differentiating through a bare ``psum`` under
``check_vma=False`` applies the psum transpose (a second psum), scaling
sharded-parameter gradients by the axis size — the bug class these wrappers
exist to prevent.  (Verified numerically in tests/test_model.py.)

Each tensor-parallel all-reduce sits between dependent matmuls, so on its
own it can only stall the chip.  The layer loop
(:func:`zhpe_ompi_tpu.models.transformer.forward_hidden`) therefore runs an
even local batch as two independent half-batch chunks when tp has two
ranks and sp is absent: one chunk's all-reduce then has the other chunk's
work beside it, forward and backward.  Both wrappers call
``comm.allreduce``; the communicator's decision layer
(:func:`zhpe_ompi_tpu.coll.tuned.decide`) issues a two-rank allreduce on a
TPU as one asynchronous swap, which is what lets it overlap.  The chunk
count is the constant ``transformer.TP_CHUNKS`` (2), not an option.
"""

from __future__ import annotations

import jax

from .. import ops as zops


def g_allreduce(comm, x, op=None):
    """Forward: comm.allreduce(x); backward: identity (cotangent passes
    through).  Use after tensor-parallel partial products."""
    op = op or zops.SUM

    @jax.custom_vjp
    def g(v):
        return comm.allreduce(v, op)

    def fwd(v):
        return g(v), None

    def bwd(_, ct):
        return (ct,)

    g.defvjp(fwd, bwd)
    return g(x)


def f_identity(comm, x, op=None):
    """Forward: identity; backward: allreduce-sum of the cotangent.  Use at
    the entry of a tensor-parallel region consuming a replicated value."""
    op = op or zops.SUM

    @jax.custom_vjp
    def f(v):
        return v

    def fwd(v):
        return v, None

    def bwd(_, ct):
        return (comm.allreduce(ct, op),)

    f.defvjp(fwd, bwd)
    return f(x)
