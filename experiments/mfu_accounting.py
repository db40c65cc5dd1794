#!/usr/bin/env python
"""Compute-efficiency (MFU) accounting for the training benchmarks.

VERDICT r3 missing #4: BASELINE.md quotes steps/s for the training configs
but never says what fraction of the v5e's bf16 peak those steps achieve —
the exchange side has a roofline story (657.5 GB/s ~= 80 % of HBM), the
compute side had none.  This experiment supplies the denominator:

- **FLOPs/step** come from XLA's own cost model:
  ``jax.jit(step).lower(state, batch).compile().cost_analysis()["flops"]``
  on the EXACT stacked train step the examples benchmark (same model, peer
  count, batch, dtype, optimizer, gossip exchange — the whole one-chip XLA
  program, so the figure includes the exchange and optimizer, not just the
  matmuls).  XLA counts 2 FLOPs per MAC (verified: a [256,256]x[256,256]
  matmul reports 2*256^3).  Lowering runs on the forced-CPU backend —
  cost_analysis is shape-derived, so no chip is needed.
- **steps/s** are the chip-measured numbers from BASELINE.md's measured
  table (round 2, a single v5e, before PR 1).  Pass
  ``--steps-per-sec name=value`` to substitute a fresh measurement.
- **MFU** = flops_per_step x steps_per_sec / 1.97e14 (v5e bf16 peak,
  ~197 TFLOP/s).  For f32 configs (BERT+AdamW) this denominator overstates
  the reachable peak — f32 multiplies pass the MXU at a fraction of bf16
  rate — so their MFU is a conservative lower bound, flagged in the
  record.

A transformer sanity estimate (6*P*tokens + 12*L*T^2*d attention term,
matmul-only, train = 3x fwd) is reported alongside the XLA figure for the
transformer configs so a unit error in either method is visible as a
ratio far from ~1.

Llama-3-8B block at real dims: with ``--llama-block``, the same XLA
accounting runs on the T=4096/8192 block train step; MFU pairs it with
``artifacts/llama_block_real_dims*.json``'s measured ``train_step_ms``
when those exist (written by ``experiments/llama_block_bench.py`` on a
live chip).

Results -> artifacts/mfu_accounting.json (+ a table printed to stderr).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

V5E_BF16_PEAK = 197e12

# Chip-measured steps/s (BASELINE.md measured table; round-2 runs on the
# single v5e, RTT-corrected, synthetic pre-staged batches).  Each entry:
# (steps_per_sec, provenance).
MEASURED = {
    "resnet20_cifar10": (
        135.2,
        "examples/cifar10/main.py --transport stacked --synthetic --bf16 "
        "(BASELINE.md r2: 8-peer ring, batch 64/peer)",
    ),
    "resnet50_imagenet": (
        21.2,
        "examples/imagenet/main.py --transport stacked --peers 8 "
        "--batch-size 8 --bf16 (BASELINE.md r2: 8-peer random-pair)",
    ),
    "bert_base_mlm": (
        4.0,
        "examples/bert/main.py --transport stacked --peers 4 --group-size 2 "
        "--batch-size 4 (BASELINE.md r2: f32 + AdamW, seq 128)",
    ),
    "llama_lora_tiny": (
        17.0,
        "examples/llama_lora/main.py --transport stacked --peers 8 "
        "(BASELINE.md r2: tiny dims d=64 — latency-bound by design)",
    ),
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def xla_flops(step_fn, *args) -> float:
    import jax

    # make_stacked_train_step returns a plain wrapper around its inner
    # jitted program; an outer jit gives it a .lower and traces straight
    # through to one whole-step XLA computation.
    if not hasattr(step_fn, "lower"):
        step_fn = jax.jit(step_fn)
    compiled = step_fn.lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca["flops"])


def transformer_analytic(
    *, p_matmul: int, tokens: int, n_layers: int, seq: int, d_model: int,
    batch_seqs: int, train_factor: float = 3.0,
) -> float:
    """Matmul-only transformer estimate: fwd = 2*P*tokens + 4*L*T^2*d per
    sequence; train = train_factor x fwd (bwd ~= 2x fwd)."""
    fwd = 2.0 * p_matmul * tokens + 4.0 * n_layers * seq * seq * d_model * batch_seqs
    return train_factor * fwd


def _build_resnet(model_name: str, n: int, b: int, img: int, schedule: str):
    """Shared scaffolding for the two ResNet rows (same loss/optimizer/
    stacked-step wiring; they differ only in model, peers, batch, image
    size, schedule — exactly the examples' benchmark settings)."""
    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.models import resnet
    from dpwa_tpu.parallel.stacked import (
        StackedTransport, init_stacked_state, make_stacked_train_step,
    )
    from dpwa_tpu.train import init_params_per_peer

    cfg = make_local_config(n, schedule=schedule)
    transport = StackedTransport(cfg)
    model = getattr(resnet, model_name)(dtype=jnp.bfloat16)
    stacked = init_params_per_peer(
        lambda k: model.init(k, jnp.zeros((1, img, img, 3))),
        jax.random.key(0), n,
    )
    opt = optax.sgd(0.1, momentum=0.9)
    state = init_stacked_state(stacked, opt, transport)

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    step = make_stacked_train_step(loss_fn, opt, transport)
    batch = (
        jnp.zeros((n, b, img, img, 3), jnp.float32),
        jnp.zeros((n, b), jnp.int32),
    )
    return step, (state, batch), {
        "peers": n, "batch_per_peer": b, "dtype": "bf16",
        "images_per_step": n * b,
    }, None


def build_resnet20():
    return _build_resnet("ResNet20", n=8, b=64, img=32, schedule="ring")


def build_resnet50():
    return _build_resnet("ResNet50", n=8, b=8, img=224, schedule="random")


def build_bert():
    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.models.bert import BertMLM, bert_base_config, mlm_loss_fn
    from dpwa_tpu.parallel.stacked import (
        StackedTransport, init_stacked_state, make_stacked_train_step,
    )
    from dpwa_tpu.train import stack_params

    n, b, t = 4, 4, 128
    cfg = make_local_config(n, schedule="hierarchical", group_size=2)
    transport = StackedTransport(cfg)
    mcfg = bert_base_config()
    model = BertMLM(mcfg)
    stacked = stack_params(
        model.init(jax.random.key(0), jnp.zeros((1, t), jnp.int32)), n
    )
    opt = optax.adamw(1e-4)
    state = init_stacked_state(stacked, opt, transport)
    step = make_stacked_train_step(mlm_loss_fn(model), opt, transport)
    batch = (
        jnp.zeros((n, b, t), jnp.int32),
        jnp.zeros((n, b, t), jnp.int32),
        jnp.zeros((n, b, t), jnp.float32),
    )
    # Analytic: BERT-base non-embedding matmul params per layer =
    # 4*d^2 (attn) + 2*d*d_ff (ffn); + the MLM head's d x vocab tied matmul.
    d, L, V = mcfg.d_model, mcfg.n_layers, mcfg.vocab_size
    p_matmul = L * (4 * d * d + 2 * d * mcfg.d_ff) + d * V + d * d
    analytic = transformer_analytic(
        p_matmul=p_matmul, tokens=n * b * t, n_layers=L, seq=t,
        d_model=d, batch_seqs=n * b,
    )
    return step, (state, batch), {
        "peers": n, "batch_per_peer": b, "seq_len": t, "dtype": "f32",
        "tokens_per_step": n * b * t,
        "f32_note": (
            "f32 matmuls reach a fraction of the bf16 MXU peak; MFU vs the "
            "bf16 denominator is a conservative lower bound"
        ),
    }, analytic


def build_llama_tiny():
    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.models.llama import (
        Llama, LlamaConfig, lora_filter, lora_optimizer,
    )
    from dpwa_tpu.parallel.stacked import (
        StackedTransport, init_stacked_state, make_stacked_train_step,
    )
    from dpwa_tpu.train import stack_params

    n, b, t = 8, 4, 64
    cfg = make_local_config(n, schedule="random", mode="pull")
    transport = StackedTransport(cfg)
    mcfg = LlamaConfig(
        vocab_size=256, d_model=64, n_layers=4, n_heads=8, n_kv_heads=4,
        d_ff=128, max_seq_len=t, lora_rank=8,
    )
    model = Llama(mcfg)
    stacked = stack_params(
        model.init(jax.random.key(0), jnp.zeros((1, t), jnp.int32)), n
    )
    opt = lora_optimizer(
        optax.adam(1e-3), jax.tree.map(lambda v: v[0], stacked)
    )
    state = init_stacked_state(stacked, opt, transport)

    def loss_fn(params, batch):
        tokens, targets = batch
        logits = model.apply(params, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets
        ).mean()

    step = make_stacked_train_step(
        loss_fn, opt, transport, exchange_filter=lora_filter
    )
    batch = (
        jnp.zeros((n, b, t), jnp.int32),
        jnp.zeros((n, b, t), jnp.int32),
    )
    return step, (state, batch), {
        "peers": n, "batch_per_peer": b, "seq_len": t, "dtype": "f32",
        "tokens_per_step": n * b * t,
        "note": "tiny dims (d=64): latency-bound by design, MFU ~0 expected",
    }, None


BUILDERS = {
    "resnet20_cifar10": build_resnet20,
    "resnet50_imagenet": build_resnet50,
    "bert_base_mlm": build_bert,
    "llama_lora_tiny": build_llama_tiny,
}


def llama_block_flops(seq_len: int) -> tuple[float, float]:
    """(xla_flops, analytic) for the real-dims Llama-3-8B block train step —
    the exact step experiments/llama_block_bench.py times on the chip."""
    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.models.llama import Block, LlamaConfig, llama3_8b_config, lora_optimizer

    full = llama3_8b_config(lora_rank=16)
    cfg = LlamaConfig(
        vocab_size=full.vocab_size, d_model=full.d_model, n_layers=1,
        n_heads=full.n_heads, n_kv_heads=full.n_kv_heads, d_ff=full.d_ff,
        max_seq_len=seq_len, rope_theta=full.rope_theta,
        lora_rank=full.lora_rank, dtype=jnp.bfloat16,
    )
    block = Block(cfg)
    x = jnp.zeros((1, seq_len, cfg.d_model), jnp.bfloat16)
    positions = jnp.arange(seq_len)
    params = block.init(jax.random.key(1), x[:, :128], positions[:128])
    opt = lora_optimizer(optax.adam(1e-4), params)
    opt_state = opt.init(params)

    @jax.jit
    def train_step(params, opt_state, x):
        def loss(p):
            out = block.apply(p, x, positions)
            return jnp.mean(out.astype(jnp.float32) ** 2)

        l, g = jax.value_and_grad(loss)(params)
        updates, opt_state = opt.update(g, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, l

    flops = xla_flops(train_step, params, opt_state, x)
    d, kvd, ff = cfg.d_model, cfg.n_kv_heads * cfg.head_dim, cfg.d_ff
    p_matmul = 2 * d * d + 2 * d * kvd + 3 * d * ff
    analytic = transformer_analytic(
        p_matmul=p_matmul, tokens=seq_len, n_layers=1, seq=seq_len,
        d_model=d, batch_seqs=1,
    )
    return flops, analytic


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--configs", nargs="*", default=list(BUILDERS),
        help="subset of configs to account",
    )
    ap.add_argument(
        "--llama-block", action="store_true",
        help="also account the real-dims Llama-3-8B block (heavy compile)",
    )
    ap.add_argument(
        "--steps-per-sec", nargs="*", default=[],
        metavar="NAME=VALUE",
        help="override the recorded steps/s with a fresh measurement",
    )
    args = ap.parse_args()

    from dpwa_tpu.utils.devices import ensure_devices

    ensure_devices(1, mode="cpu")  # cost_analysis only — no chip needed

    overrides = {}
    for spec in args.steps_per_sec:
        name, _, val = spec.partition("=")
        overrides[name] = float(val)

    results = {}
    for name in args.configs:
        log(f"[{name}] building + lowering ...")
        step, step_args, meta, analytic = BUILDERS[name]()
        flops = xla_flops(step, *step_args)
        sps, prov = MEASURED[name]
        if name in overrides:
            sps, prov = overrides[name], "--steps-per-sec override"
        tflops = flops * sps / 1e12
        rec = {
            **meta,
            "flops_per_step_xla": flops,
            "steps_per_sec": sps,
            "steps_per_sec_source": prov,
            "achieved_tflops": round(tflops, 3),
            "mfu_vs_bf16_peak_pct": round(100 * tflops * 1e12 / V5E_BF16_PEAK, 3),
        }
        if analytic is not None:
            rec["flops_per_step_analytic"] = analytic
            rec["xla_over_analytic"] = round(flops / analytic, 3)
        results[name] = rec
        log(
            f"[{name}] {flops/1e9:.2f} GFLOP/step x {sps} steps/s = "
            f"{tflops:.2f} TFLOP/s = {rec['mfu_vs_bf16_peak_pct']:.2f}% of "
            "v5e bf16 peak"
        )

    if args.llama_block:
        for t in (4096, 8192):
            log(f"[llama_block T={t}] lowering (heavy) ...")
            flops, analytic = llama_block_flops(t)
            rec = {
                "seq_len": t,
                "flops_per_step_xla": flops,
                "flops_per_step_analytic": analytic,
                "xla_over_analytic": round(flops / analytic, 3),
            }
            # Pair with a chip-measured step time when the block bench ran.
            for art in (
                f"llama_block_real_dims_T{t}.json", "llama_block_real_dims.json",
            ):
                p = os.path.join(REPO, "artifacts", art)
                if os.path.exists(p):
                    with open(p) as f:
                        data = json.load(f)
                    if data.get("block", {}).get("seq_len") == t and data.get(
                        "backend"
                    ) == "tpu":
                        ms = data["block"]["train_step_ms"]
                        tflops = flops / (ms / 1e3) / 1e12
                        rec.update(
                            {
                                "train_step_ms_measured": ms,
                                "achieved_tflops": round(tflops, 3),
                                "mfu_vs_bf16_peak_pct": round(
                                    100 * tflops * 1e12 / V5E_BF16_PEAK, 3
                                ),
                                "measured_source": art,
                            }
                        )
                        break
            if "train_step_ms_measured" not in rec:
                rec["note"] = (
                    "no chip-measured train_step_ms yet; "
                    "flops recorded so MFU drops out the moment "
                    "llama_block_bench lands"
                )
            results[f"llama3_8b_block_T{t}"] = rec
            log(f"[llama_block T={t}] {flops/1e12:.3f} TFLOP/step")

    path = os.path.join(REPO, "artifacts", "mfu_accounting.json")
    # Partial invocations (--configs subset, --llama-block alone) MERGE
    # into the existing artifact — an accounting re-run of one config must
    # never silently drop the others' rows.
    existing = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                existing = json.load(f).get("configs", {})
        except (OSError, json.JSONDecodeError):
            existing = {}
    out = {
        "experiment": "mfu_accounting",
        "peak_tflops_bf16_v5e": V5E_BF16_PEAK / 1e12,
        "flops_convention": "XLA cost_analysis, 2 FLOPs per MAC (verified)",
        "method": (
            "flops from lower().compile().cost_analysis() of the exact "
            "stacked train step (model + optimizer + gossip exchange, all "
            "peers, one XLA program); steps/s from the chip-measured "
            "BASELINE.md table"
        ),
        "configs": {**existing, **results},
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".tmp", "w") as f:
        json.dump(out, f, indent=1)
    os.replace(path + ".tmp", path)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
