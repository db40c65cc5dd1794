"""Ulysses-style all-to-all sequence parallelism (head-sharded attention).

The second of the two standard long-context strategies (the build mandate
names "ring attention or all-to-all sequence/context parallelism"; the
ring lives in :mod:`dpwa_tpu.ops.ring_attention` / ``flash_ring`` /
``zigzag_ring``).  Instead of rotating K/V blocks, DeepSpeed-Ulysses-style
SP re-shards around attention itself:

1. the model runs sequence-sharded (each device: ``[B, T_local, H, D]``);
2. ``lax.all_to_all`` re-shards q/k/v to HEAD-sharded with the FULL
   sequence per device (``[B, T_global, H/sp, D]``);
3. each device runs ordinary single-device causal attention over its
   heads — on TPU the same Pallas flash kernels as the single-device model
   path (:func:`single_device_attention` picks them by shape), O(T) memory
   via VMEM score tiles;
4. a second ``all_to_all`` returns to sequence-sharded layout.

Trade-offs vs the ring: two all-to-alls per attention instead of n
ppermutes (cheaper on all-to-all-friendly fabrics, and attention itself
is then embarrassingly parallel over heads with NO causality cases), but
per-device activations grow to O(T_global · H/sp) and the head count
bounds sp (``H % sp == 0``).  Everything is built from differentiable
collectives + library attention, so autodiff needs no custom VJP —
gradient parity is tested, not hand-derived.

GQA: grouped K/V all-to-all directly when ``KV % sp == 0`` (each device
gets KV/sp groups — the wire stays grouped); otherwise K/V heads are
expanded to H before the exchange.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def ulysses_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = "sp",
    causal: bool = True,
    impl: str = "auto",
) -> jnp.ndarray:
    """Call INSIDE shard_map over ``axis_name``.

    Same contract as
    :func:`dpwa_tpu.ops.ring_attention.ring_attention_local`: q/k/v are
    this device's CONTIGUOUS sequence block ``[B, T_local, H, D]``
    (grouped K/V heads allowed), device i holding global positions
    ``[i·T_local, (i+1)·T_local)``; returns the local output block.

    ``impl``: "auto" uses the Pallas flash kernel for the per-device
    attention on TPU when shapes allow; "dense"/"xla" forces the einsum
    reference; "flash" forces the kernel (TPU only).
    """
    n = lax.axis_size(axis_name)
    B, T, H, D = q.shape
    KV = k.shape[2]
    if H % n:
        raise ValueError(
            f"ulysses needs n_heads {H} divisible by sp={n} "
            "(attention is head-sharded after the all-to-all)"
        )
    if KV % n:
        # Too few KV groups to shard: expand to full heads first (GQA's
        # wire saving is lost, correctness is not).
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        KV = H

    # Sequence-sharded -> head-sharded with the full sequence:
    # split the heads axis n ways, concatenate received blocks along T.
    def seq_to_heads(t):
        return lax.all_to_all(
            t, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    qh = seq_to_heads(q)  # [B, T_global, H/n, D]
    kh = seq_to_heads(k)  # [B, T_global, KV/n, D]
    vh = seq_to_heads(v)

    out = single_device_attention(qh, kh, vh, causal=causal, impl=impl)

    # Head-sharded -> sequence-sharded (the inverse exchange).
    return lax.all_to_all(
        out, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def _largest_block(T: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``T`` and is at most
    ``cap``; 128 itself where T has no larger such divisor."""
    top = max(min(T, cap), 128)
    return max(b for b in range(128, top + 1, 128) if T % b == 0)


def _flash_block_sizes(T: int, D: int):
    """Block sizes for the library's flash forward, dkv and dq kernels at
    sequence length ``T`` and head size ``D``, from the sweep on the v5e in
    PERF.md (section 6, PR 25): q and k-major blocks up to 1024 with inner
    blocks up to 512, each the largest multiple of 128 that divides ``T``.

    dq alone grows along q only, to 2048: the library materialises ``di``
    in HBM at ``[B, H, T, block_k_major_dq]`` float32, so a wider k-major
    there raises the step's peak memory (1.2 % at 256 in the T 512 cell).
    Every kernel's tiles grow with ``D``: above 256 the caps shrink by
    ``ceil(D / 256)`` so that they still fit the scoped VMEM."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    shrink = -(-D // 256)
    major = _largest_block(T, 1024 // shrink)
    minor = _largest_block(major, 512 // shrink)
    return BlockSizes(
        block_q=major, block_k_major=major, block_k=minor, block_b=1,
        block_q_major_dkv=major, block_k_major_dkv=major,
        block_q_dkv=minor, block_k_dkv=minor,
        block_q_dq=_largest_block(T, 2048 // shrink),
        block_k_major_dq=128, block_k_dq=128,
    )


def single_device_attention(
    q, k, v, *, causal: bool, window: Optional[int] = None,
    impl: str = "auto", sm_scale=None
):
    """THE single-device attention of the framework, shared by the Llama
    model's non-sp path and the a2a strategy's per-device compute:
    [B, T, h, D] layout, keys and values grouped or not.  ``q`` and ``k``
    share one head size and ``v`` may have another (latent attention: 192
    and 128); ``sm_scale`` multiplies the scores (``1 / sqrt(q's head size)``
    where not given).  ``impl``: "flash" forces the Pallas kernels, "auto"
    uses them on TPU when T fits their tiling (a multiple of 128), anything
    else runs the masked-softmax einsum with f32 accumulation.

    Two families of kernels, picked by the call's shapes alone:

    - **ours** (``ops/eva.causal_attention``: the EVA core's two kernels with
      a query's earlier windows seen by their keys themselves) where the
      call is causal, ``q k v`` share a head size that is a multiple of 128,
      ``T`` is a multiple of 128 and the backward call's blocks of a whole
      head fit the VMEM it may ask for (``ops/eva.causal_kernels_take``: at a
      head of 128 up to T 8,192).  ``q`` and ``k`` are turned heads first,
      which is how XLA:TPU lays the rope's result out anyway; ``v`` goes in as
      it came and ``o`` comes back so, positions before heads, read and
      written as ``[B, T, h D]`` with a head a block of ``D`` lanes, so no
      pass turns ``v``, ``o`` or their gradients (PERF.md section 6, PR 54).
      The keys stay grouped (no ``jnp.repeat``: query head ``h`` reads block
      ``h // (h / kv)``), one forward and one backward kernel (``QK^T`` once),
      the log-sum-exp and ``di`` one ``[1, T]`` row a head, ``dk`` / ``dv``
      summed over a group in float32 in VMEM (PERF.md section 6, PR 46).
    - **the library's** flash kernels for every other shape (a head of 64,
      192 / 128, ``causal=False``, a longer ``T``), GQA expanded here first,
      ``q k v`` turned heads first and ``o`` back: its kernels want that.
      They take one head size: a multiple of 128, or 64 as it is (a block's
      64 lanes are half a register row; on the v5e at 2 x 32 x 4,096 x 64
      they give what the same heads zero-padded to 128 give, bit for bit in
      a whole step's loss, in the same 16.7 ms forward and backward, and the
      step saves the pad and the slice: PERF.md section 6, PR 44).  Where the
      head sizes differ or are neither, q, k and v are zero-padded to the
      next multiple of 128 and the output is sliced back: exact, because a
      zero column adds nothing to a score or to a value, and paid for in the
      kernels' arithmetic (192 / 128 run as 256 / 256).  Their forward, dkv
      and dq kernels run with the block sizes :func:`_flash_block_sizes`
      picks from this call's own ``T`` and ``head_dim`` (the library's
      default is 128 for every block, which at T 4096 pays a grid step's
      overhead eight times for each step's arithmetic); the sweep on the v5e
      behind the rule is PERF.md, section 6, PR 25.

    No head size falls to the einsum silently on a TPU whose tiling T fits:
    at T 4,096 its float32 scores are 4.3 GB for two sequences of 32
    heads.

    ``window`` (a sliding window: query ``t`` sees the keys ``t - window +
    1 .. t``, its own among them; causal calls alone) is **our** family's:
    where its kernels take the call (as above, and a window that is a
    multiple of 128) they visit, for a block of queries, only the key blocks
    that hold a key of the band, under the names
    ``flash_attention_fwd_dpwa_window`` / ``flash_mha_bwd_dpwa_window``.  The
    library's kernels know no window: every other windowed call (a head of
    64, a ``T`` or a window off the tiling, off the TPU) is the masked
    einsum, and ``impl="flash"`` on such a call is refused."""
    from dpwa_tpu.ops import eva

    B, T, h, D = q.shape
    Dv = v.shape[-1]
    if window is not None and not (causal and window >= 1):
        raise ValueError("a window is a causal call's, of at least one key")
    use_flash = impl == "flash" or (
        impl == "auto" and jax.default_backend() == "tpu" and T % 128 == 0
    )
    scale = float(1.0 / (D ** 0.5) if sm_scale is None else sm_scale)
    if use_flash and causal and D == Dv and eva.causal_kernels_take(
        T, D, h, k.shape[2], q.dtype, window
    ):
        heads_first = lambda x: x.transpose(0, 2, 1, 3)
        # Without a window the call is made as it always was.
        how = {} if window is None else dict(window=window)
        return eva.causal_attention(
            heads_first(q), heads_first(k), v, scale, **how
        )
    if window is not None:
        if impl == "flash":
            raise ValueError(
                f"no flash kernel takes a window of {window} at T {T}, heads "
                f"of {D} / {Dv}"
            )
        use_flash = False
    if k.shape[2] != h:
        rep = h // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if use_flash:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention,
        )

        padded = -(-max(D, Dv) // 128) * 128
        as_it_is = D == Dv and D in (padded, 64)
        if not as_it_is:
            q, k, v = (
                jnp.pad(x, [(0, 0)] * 3 + [(0, padded - x.shape[-1])])
                for x in (q, k, v)
            )
        out = flash_attention(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=causal,
            sm_scale=scale,
            block_sizes=_flash_block_sizes(T, q.shape[-1]),
        ).transpose(0, 2, 1, 3)
        return out if as_it_is else out[..., :Dv]
    s = jnp.einsum(
        "bthd,bshd->bhts",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
    )
    if sm_scale is None:
        s = s / jnp.sqrt(D).astype(jnp.float32)
    else:
        s = s * jnp.float32(sm_scale)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((T, T), bool), -window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum(
        "bhts,bshd->bthd", p, v.astype(jnp.float32)
    ).astype(q.dtype)
