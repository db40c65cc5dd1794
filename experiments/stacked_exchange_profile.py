"""What does the gossip exchange cost inside a REAL stacked train step?

VERDICT r1 weak-spot #2: the bandwidth-optimal Pallas pair-merge kernel
(`dpwa_tpu.ops.merge.pallas_pair_merge`, 2 HBM ops/row) was only exercised
by the standalone bandwidth bench, while the stacked trainer merges via the
XLA gather formulation (3 HBM ops/row).  This experiment measures, on real
hardware, whether that matters at the scales the BASELINE configs train:

- **ResNet-50 x 8 virtual peers** (config 3's model on the single-chip
  transport): full-tree exchange, ~25.6M params/peer — the largest payload
  any config gossips every step.
- **Llama + LoRA subset exchange** (config 5): only adapter leaves gossip.

For each it reports the median time of (a) the full stacked train step,
(b) a local-only step (identical math minus the exchange), (c) the jitted
exchange alone, and (d) `pallas_pair_merge` streaming the same payload as
one flat [n, d] buffer — the kernel's best case.  The decision rule is in
the printed summary: the exchange's share of the step, and the end-to-end
ceiling from swapping in the Pallas kernel (saves 1 of the 3 HBM passes,
IF the pytree could be carried flat — leaf-wise grafting adds reshape
copies that cost more than the pass it saves).

Run on the TPU chip:  python experiments/stacked_exchange_profile.py
Writes artifacts/stacked_exchange_profile.json.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def profile_config(name, init_fn, loss_fn, batch_fn, n, exchange_filter,
                   iters):
    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.interpolation import PeerMeta
    from dpwa_tpu.ops.merge import involution_pairs, pallas_pair_merge
    from dpwa_tpu.parallel.stacked import (
        StackedTransport,
        init_stacked_state,
        make_stacked_train_step,
    )
    from dpwa_tpu.train import init_params_per_peer
    from dpwa_tpu.utils.profiling import timed_loop
    from dpwa_tpu.utils.pytree import partition, tree_size_bytes

    cfg = make_local_config(n, schedule="ring")
    transport = StackedTransport(cfg)
    stacked = init_params_per_peer(init_fn, jax.random.key(0), n)
    # init_stacked_state takes ownership of what it is given.
    own = lambda tree: jax.tree.map(jnp.copy, tree)
    opt = optax.sgd(0.1, momentum=0.9)
    state = init_stacked_state(own(stacked), opt, transport)

    # (a) the real train step: local update + exchange, one program.
    step_fn = make_stacked_train_step(
        loss_fn, opt, transport, exchange_filter=exchange_filter
    )

    # (b) local-only twin: identical math with the exchange deleted.
    grad_fn = jax.value_and_grad(loss_fn)

    def per_peer(params, opt_state, batch):
        loss, grads = grad_fn(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    @functools.partial(jax.jit, donate_argnums=(0,))
    def local_step(state, batch):
        params, opt_state, losses = jax.vmap(per_peer)(
            state.params, state.opt_state, batch
        )
        return state._replace(
            params=params, opt_state=opt_state,
            clock=state.clock + 1.0, step=state.step + 1,
        ), losses

    # (c) the exchange alone, on the exchanged subset of the real pytree.
    if exchange_filter is not None:
        exchanged, _ = partition(state.params, exchange_filter)
    else:
        exchanged = state.params
    payload = tree_size_bytes(jax.tree.map(lambda v: v[0], exchanged))
    meta = PeerMeta(jnp.ones(n), jnp.ones(n))

    batch = batch_fn()

    # One live replica-state at a time: a second full (params + momentum)
    # copy of the larger configs does not fit the chip's HBM.
    t_full, out = timed_loop(
        lambda c, k: step_fn(c[0], batch)[:2],
        (state, jnp.zeros(n)), iters,
    )
    del state, out
    # (a') overlap mode: exchange of x_k runs concurrently with fwd/bwd.
    overlap_step = make_stacked_train_step(
        loss_fn, opt, transport, exchange_filter=exchange_filter,
        overlap=True,
    )
    state_o = init_stacked_state(own(stacked), opt, transport)
    t_overlap, out = timed_loop(
        lambda c, k: overlap_step(c[0], batch)[:2],
        (state_o, jnp.zeros(n)), iters,
    )
    del state_o, out
    state2 = init_stacked_state(own(stacked), opt, transport)
    t_local, out = timed_loop(
        lambda c, k: local_step(c[0], batch),
        (state2, jnp.zeros(n)), iters,
    )
    del state2, out
    state3 = init_stacked_state(own(stacked), opt, transport)
    if exchange_filter is not None:
        exchanged3, _ = partition(state3.params, exchange_filter)
    else:
        exchanged3 = state3.params
    del state3
    t_exch, out = timed_loop(
        lambda p, k: transport.exchange(p, meta, k)[0],
        exchanged3, iters,
    )
    del exchanged3, out

    # (d) the Pallas kernel's best case: the same bytes as ONE flat
    # [n, rows, 128] resident buffer, merged in place (2 HBM ops/row).
    # Grain = 128 lanes x 1024 rows so the kernel's row count factors into
    # full-size blocks (a payload rounded to a near-prime row count would
    # degrade it to slivers and understate the kernel).
    lanes = 128
    grain = lanes * 1024
    d = (payload // 4 + grain - 1) // grain * grain
    buf = jnp.ones((n, d // lanes, lanes), jnp.float32)
    left, right = involution_pairs(transport.schedule.pool[0])
    alpha = jnp.full((n,), 0.5, jnp.float32)
    on_tpu = jax.default_backend() == "tpu"

    t_pallas, buf = timed_loop(
        lambda b, k: pallas_pair_merge(
            b, left, right, alpha, interpret=not on_tpu
        ),
        buf, iters,
    )
    del buf

    exch_in_step = max(t_full - t_local, 0.0)
    result = {
        "config": name,
        "backend": jax.default_backend(),
        "n_peers": n,
        "payload_mb_per_peer": payload / 1e6,
        "t_full_step_ms": t_full * 1e3,
        "t_overlap_step_ms": t_overlap * 1e3,
        "t_local_step_ms": t_local * 1e3,
        "t_exchange_in_step_ms": exch_in_step * 1e3,
        "t_exchange_alone_ms": t_exch * 1e3,
        "t_pallas_flat_ms": t_pallas * 1e3,
        "exchange_fraction_of_step": exch_in_step / t_full if t_full else 0,
        # Fraction of the step the overlap mode actually recovers.
        "overlap_recovered_fraction": max(t_full - t_overlap, 0.0) / t_full
        if t_full
        else 0,
        # If the exchange ran at the Pallas kernel's rate instead, the step
        # would shrink by at most this fraction (flat-buffer best case).
        "pallas_endtoend_ceiling": max(exch_in_step - t_pallas, 0.0)
        / t_full
        if t_full
        else 0,
    }
    print(json.dumps(result, indent=2))
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--peers", type=int, default=8)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--skip-lora", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dpwa_tpu.models.llama import Llama, LlamaConfig, lora_filter
    from dpwa_tpu.models.resnet import ResNet50

    print(f"backend: {jax.default_backend()}", file=sys.stderr)
    n, S, B = args.peers, args.image_size, args.batch_size
    rng = np.random.default_rng(0)
    results = []

    model = ResNet50()

    def resnet_loss(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    results.append(
        profile_config(
            "resnet50-fulltree",
            lambda k: model.init(k, jnp.zeros((1, S, S, 3))),
            resnet_loss,
            lambda: (
                jnp.asarray(rng.random((n, B, S, S, 3), np.float32)),
                jnp.asarray(rng.integers(0, 1000, (n, B)).astype(np.int32)),
            ),
            n, None, args.iters,
        )
    )

    if not args.skip_lora:
        # Scaled-down Llama (a full 8B does not fit 8x on one chip) with
        # the real LoRA subset-exchange: the point is the payload RATIO.
        lcfg = LlamaConfig(
            vocab_size=8192, d_model=1024, n_layers=4, n_heads=8,
            n_kv_heads=4, d_ff=2816, max_seq_len=512, lora_rank=16,
        )
        lmodel = Llama(lcfg)
        T = 256

        def llama_loss(params, tokens):
            logits = lmodel.apply(params, tokens[:, :-1])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tokens[:, 1:]
            ).mean()

        results.append(
            profile_config(
                "llama-lora-subset",
                lambda k: lmodel.init(k, jnp.zeros((1, 8), jnp.int32)),
                llama_loss,
                lambda: jnp.asarray(
                    rng.integers(
                        0, lcfg.vocab_size, (n, 2, T + 1)
                    ).astype(np.int32)
                ),
                n, lora_filter, args.iters,
            )
        )

    out = os.path.join(
        REPO_ROOT, "artifacts", "stacked_exchange_profile.json"
    )
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
