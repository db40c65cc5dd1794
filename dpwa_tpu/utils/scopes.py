"""Names for the parts of a train step, as ``jax.named_scope`` metadata.

Three scopes give the step's four phases: ``dpwa.forward`` (two of them, as
below), ``dpwa.optimizer`` and ``dpwa.exchange``.  Twenty more lie inside
the forward scope and name the parts of a decoder (``models/llama.py``):
attention plain (a sliding-window layer under one more name inside it), latent
and EVA (with its summaries and its core), the gated
short convolution and its gate, the dense feed-forward, the expert layer's
three parts, the state-space mixer with its scan and the four parts around
it (projections, convolution, step size, gate), the head, the loss.  The outer
norms, the embedding and the residual adds carry none: they are what is left
under ``dpwa.forward``.  A scope's name becomes a component of the
``op_name`` of every HLO instruction traced under it, and JAX wraps the name
when it differentiates: with the loss call under ``dpwa.forward`` inside
``jax.value_and_grad``, forward instructions carry ``jvp(dpwa.forward)`` and
backward instructions ``transpose(jvp(dpwa.forward))``; what a
``jax.checkpoint`` runs again carries ``rematted_computation`` besides.  The
names are metadata only: they change no arithmetic and cost nothing when no
profiler runs.  ``benchmark/scopes.py`` reads the phases back from a device
trace and ``benchmark/block_scopes.py`` the parts of the decoder.

Not a tracing system (that is :mod:`dpwa_tpu.obs`, on the host): only names.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

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
# A plain attention call whole (``models/llama.Attention``, put on in
# ``Block``): ``wq wk wv`` with their adapters, ``q_norm`` / ``k_norm`` where
# the configuration has them, rope, the core (``single_device_attention`` or
# an sp strategy) and ``wo``.  Latent attention has its own name, not both.
ATTN_GQA = "dpwa.attn.gqa"
# Latent attention whole (``models/llama.LatentAttention``): the down and up
# projections with their norms, rope, the attention core, the output
# projection.
ATTN_LATENT = "dpwa.attn.latent"
# EVA attention (``models/llama.EvaAttention``, put on in ``Block``): ``whole``
# is ``wq wk wv`` with their adapters, the turn to heads first, rope, the
# chunk summaries, the core, the turn back and ``wo``; inside it ``summaries``
# alone (``ops/eva.chunk_summaries``: a chunk's softmax and the two pooled
# sums) and ``core`` alone (``ops/eva.eva_attention``: the forward and
# backward kernels with the row sums beside them, or the plain windowed form).
# One value of three fields and not three plain constants: the accepted
# ``benchmark/block_scopes.GROUPS`` does not know these names yet, and its
# test holds the table to every plain string constant of this module
# (PERF.md section 7 asks a ``benchmark`` PR for the three rows).
class _EvaNames(NamedTuple):
    whole: str = "dpwa.attn.eva"
    summaries: str = "dpwa.attn.eva.summaries"
    core: str = "dpwa.attn.eva.core"


ATTN_EVA = _EvaNames()
# A gated short convolution whole (``models/llama.ShortConv``): ``in_proj``
# and ``out_proj`` with their adapters and everything between them; inside it
# ``gate`` alone: ``b * u``, the taps of the causal convolution and ``c * z``,
# elementwise work between the two projections.  One value of two fields, for
# the reason ``ATTN_EVA`` is one of three (PERF.md section 7 asks for the
# rows).
class _ConvNames(NamedTuple):
    whole: str = "dpwa.conv"
    gate: str = "dpwa.conv.gate"


CONV = _ConvNames()
# A sliding-window attention layer whole (``models/llama.Attention`` with
# ``kind="sliding_attention"``, put on in ``Block`` *inside* ``dpwa.attn.gqa``,
# which stays around plain attention of either kind): the projections, the
# norms a head, the layer's own rope, the windowed core and ``wo``.  A value
# with a field, for the reason ``ATTN_EVA`` is one (PERF.md section 7).
class _WindowNames(NamedTuple):
    whole: str = "dpwa.attn.window"


ATTN_WINDOW = _WindowNames()
# A dense SwiGLU feed-forward whole (``models/llama.MLP`` as a layer's
# feed-forward, put on in ``Block``): ``w_gate``, ``w_up``, ``silu x up``,
# ``w_down`` and their adapters.  The shared expert is an ``MLP`` too and
# stays under ``dpwa.moe.shared`` alone.
MLP = "dpwa.mlp"
# A state-space mixer whole (``models/llama.MambaMixer``): projections,
# convolution, inner norms, scan and gate; and inside it the selective scan
# alone (``ops/ssm.py``: the discretisation and the recurrence, forward and
# backward kernels).
SSM = "dpwa.ssm"
SSM_SCAN = "dpwa.ssm.scan"
# What a mixer does around its scan, each inside ``dpwa.ssm`` and beside
# ``dpwa.ssm.scan``: ``proj`` the four projections with their adapters
# (``in_proj``, ``x_proj``, ``dt_proj``, ``out_proj``; the module's own name is
# the next component of the ``op_name``); ``conv`` the split of ``in_proj``'s
# product, the causal depthwise convolution and silu; ``dt`` the three inner
# norms, the cast to float32, ``dt_bias``, softplus and ``-exp(A_log)``;
# ``gate`` ``y * silu(z)``.  None holds ``dpwa.ssm.scan`` as a substring
# (``benchmark/ssm_scopes.py`` matches by substring).  One value of four
# fields, for the reason ``ATTN_EVA`` is one of three (PERF.md section 7 asks
# for the rows).
class _SsmPartNames(NamedTuple):
    proj: str = "dpwa.ssm.proj"
    conv: str = "dpwa.ssm.conv"
    dt: str = "dpwa.ssm.dt"
    gate: str = "dpwa.ssm.gate"


SSM_PARTS = _SsmPartNames()
# The projection to the vocabulary (``models/llama.Llama``): ``lm_head``, or
# ``x E^T`` where the embedding is tied.  ``final_norm`` stays outside.
HEAD = "dpwa.head"
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
