"""Names for the parts of a train step, as ``jax.named_scope`` metadata.

Three scopes give four phases.  A scope's name becomes part of the
``op_name`` of every HLO instruction traced under it, and JAX wraps the name
when it differentiates: with the loss call under ``dpwa.forward`` inside
``jax.value_and_grad``, forward instructions carry ``jvp(dpwa.forward)`` and
backward instructions ``transpose(jvp(dpwa.forward))``.  The names are
metadata only: they change no arithmetic and cost nothing when no profiler
runs.  ``benchmark/scopes.py`` reads them back from a device trace.

Not a tracing system (that is :mod:`dpwa_tpu.obs`, on the host): only names.
"""

from __future__ import annotations

import functools

import jax

FORWARD = "dpwa.forward"
OPTIMIZER = "dpwa.optimizer"
EXCHANGE = "dpwa.exchange"
# Inside the forward scope, the two halves of a sparse-expert layer
# (``ops/moe.py``): router, softmax, top-k, sort, gathers and combine; and
# the grouped matmuls with their adapters.  They nest under ``dpwa.forward``,
# so the phases above book them as forward / backward as before.
MOE_ROUTE = "dpwa.moe.route"
MOE_EXPERTS = "dpwa.moe.experts"
# Beside them, the shared expert that every token takes (a dense SwiGLU); the
# two names above keep their meaning for the experts a replica holds.
MOE_SHARED = "dpwa.moe.shared"
# Latent attention whole (``models/llama.LatentAttention``): the down and up
# projections with their norms, rope, the attention core, the output
# projection.
ATTN_LATENT = "dpwa.attn.latent"
# A state-space mixer whole (``models/llama.MambaMixer``): projections,
# convolution, inner norms, scan and gate; and inside it the selective scan
# alone (``ops/ssm.py``: the discretisation and the recurrence, forward and
# backward kernels).
SSM = "dpwa.ssm"
SSM_SCAN = "dpwa.ssm.scan"
# Likewise nested: the cross-entropy over the vocabulary and its gradient.
LOSS = "dpwa.loss"


def scoped(name: str):
    """Decorator: every call of the function is traced under ``name``."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


def scoped_loss(loss_fn):
    """``loss_fn`` with its whole call under :data:`FORWARD`; hand the result
    to ``jax.value_and_grad`` and the backward pass names itself."""
    return scoped(FORWARD)(loss_fn)
