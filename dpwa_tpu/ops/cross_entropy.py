"""Integer-label softmax cross-entropy with no gather and no scatter.

``optax.softmax_cross_entropy_with_integer_labels`` picks the label's logit
with ``take_along_axis``, whose gradient is a scatter-add into the float32
gradient of the logits.  Over a vocabulary axis XLA:TPU serves that scatter by
relaying the whole ``[..., vocab]`` array out to a flat layout and back (two
passes over 1.65 GB for 8,192 updates at 50,304 columns).  Here the label's
logit is picked by comparison, so the gradient ``softmax - onehot`` is a
compare-and-select that fuses into whatever consumes it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def softmax_cross_entropy(logits, targets):
    """``logsumexp(logits) - logits[target]`` over the last axis, in float32.

    ``logits`` ``[..., vocab]`` float, ``targets`` ``[...]`` int in ``[0,
    vocab)``; returns the per-position losses ``[...]``, as
    ``optax.softmax_cross_entropy_with_integer_labels`` does.
    """
    logits = logits.astype(jnp.float32)
    at_label = jnp.arange(logits.shape[-1]) == targets[..., None]
    label_logits = jnp.where(at_label, logits, 0.0).sum(-1)
    return jax.nn.logsumexp(logits, axis=-1) - label_logits
