"""Matmuls with narrow operands and a wide result, so that a value is rounded
in one place only: where it enters a matmul.

A model computed in bfloat16 throughout is not one function on a TPU.  XLA
keeps a matmul's float32 accumulator for whatever elementwise work it fuses
behind it (``xla_allow_excess_precision``), so whether ``x W + (x A) B`` is
rounded once or twice depends on what was fused, and that differs between the
same model under ``vmap`` over peers and unbatched: at the published widths
of the latent-attention cell a third of a projection's outputs came out one
bfloat16 step apart, and by the first expert layer 15 % of the tokens chose
another top-8 of 192 (PERF.md section 6, PR 32).  Where the activations
between matmuls are float32 (``LlamaConfig.activation_dtype``) what a program
computes no longer depends on what was fused: :func:`narrow` rounds a value
to the matmul type with an instruction the compiler may not drop,
:func:`wide_dot` multiplies narrow operands into a float32 result, forward
and backward.  Two programs then differ by the order of their float32 sums
alone (1 % of the tokens in the first expert layer, 7 % in the fourth)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def narrow(x, dtype):
    """``x`` rounded to ``dtype`` (to nearest even), as that type.  The
    rounding is a ``reduce_precision``, which XLA keeps where it would drop
    a pair of conversions; ``x`` of that type already comes back as it is."""
    if x.dtype == dtype:
        return x
    info = jnp.finfo(dtype)
    return lax.reduce_precision(x, info.nexp, info.nmant).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def wide_dot(x, w, dtype, out_dtype):
    """``x [..., K] @ w [K, N]`` with both operands rounded to ``dtype`` and
    the result in ``out_dtype``.  The gradients are matmuls of the same kind:
    the cotangent is rounded to ``dtype`` once, and both products come out in
    ``out_dtype`` (then in the type of the operand they belong to)."""
    return jnp.dot(
        narrow(x, dtype), narrow(w, dtype), preferred_element_type=out_dtype
    )


def _wide_dot_fwd(x, w, dtype, out_dtype):
    # The narrow ``x`` is what the backward pass multiplies; an empty array
    # carries the type ``x`` came in.
    return wide_dot(x, w, dtype, out_dtype), (
        narrow(x, dtype), w, jnp.zeros((0,), x.dtype)
    )


def _wide_dot_bwd(dtype, out_dtype, residuals, grad):
    x, w, like_x = residuals
    grad = narrow(grad, dtype)
    d_x = lax.dot_general(  # grad w^T, with no transpose written
        grad, narrow(w, dtype), (((grad.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=out_dtype,
    )
    d_w = lax.dot_general(
        x.reshape(-1, x.shape[-1]), grad.reshape(-1, grad.shape[-1]),
        (((0,), (0,)), ((), ())), preferred_element_type=out_dtype,
    )
    return d_x.astype(like_x.dtype), d_w.astype(w.dtype)


wide_dot.defvjp(_wide_dot_fwd, _wide_dot_bwd)
