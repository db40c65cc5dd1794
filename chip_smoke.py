#!/usr/bin/env python3
"""The standing proof that the gossip trainer still starts on the chip.

One process, no subprocess.  Every leg drives the path an example takes —
``make_local_config`` -> ``utils.launch.build_transport`` ->
``bundle.init_state`` -> ``bundle.make_step`` — at the full width of a model
the repo supports, takes a few steps on seeded random data, and checks the
result by the repo's own means.  Legs that need more chips than the host has
are skipped and say so.

    python chip_smoke.py                 # on a TPU host: 1 chip or 4
    python chip_smoke.py --rehearse-cpu  # toy shapes, emulated CPU mesh

Without ``--rehearse-cpu`` the script exits non-zero, with no result line,
unless ``jax.devices()[0].platform == "tpu"``.  Each leg prints one JSON line
(times are observations, not metrics); the last line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}`` and the exit code is
0 only when no leg failed.  Tracebacks also go to ``chiprun_out/``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 20260926
RESNET_LR = 0.002  # small enough that momentum does not overshoot in 6 steps
EXIT_FAILED, EXIT_NO_ACCELERATOR = 1, 4


class Sizes:
    """The shapes of one run: the chip's, or the CPU rehearsal's toys."""

    def __init__(self, rehearsal: bool):
        import jax.numpy as jnp

        self.rehearsal = rehearsal
        if rehearsal:
            self.resnet_stages = (1, 1, 1, 1)
            self.image, self.resnet_batch = 32, 2
            self.resnet_dtype = jnp.float32
            self.llama = dict(
                vocab_size=256, d_model=256, n_heads=2, n_kv_heads=1,
                d_ff=256, n_layers=1, dtype=jnp.float32,
            )
            self.seq_len, self.llama_batch = 128, 1
            self.steps = 2
        else:
            # ResNet-50 and the decoder at published width; only the
            # decoder's depth is cut (2 layers).
            self.resnet_stages = (3, 4, 6, 3)
            self.image, self.resnet_batch = 224, 8
            self.resnet_dtype = jnp.bfloat16
            self.llama = dict(
                vocab_size=32000, d_model=1024, n_heads=8, n_kv_heads=4,
                d_ff=3072, n_layers=2, dtype=jnp.bfloat16,
            )
            self.seq_len, self.llama_batch = 1024, 2
            self.steps = 5


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------


def kernel_mode(sz: Sizes):
    """On the chip kernels compile; the rehearsal interprets them."""
    import contextlib

    from jax.experimental.pallas import tpu as pltpu

    if sz.rehearsal:
        return pltpu.force_tpu_interpret_mode()
    return contextlib.nullcontext()


def hlo_text(fn, *args) -> str:
    """HLO text of the program ``fn`` traces to at these arguments.

    The kernel dispatchers pick an implementation from the backend and give
    way silently; the lowered program is what says which one ran."""
    import jax
    from jax.sharding import NamedSharding

    def spec(x):
        sh = getattr(x, "sharding", None)
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=sh if isinstance(sh, NamedSharding) else None,
        )

    return jax.jit(fn).lower(*jax.tree.map(spec, args)).as_text(dialect="hlo")


def run_steps(step_fn, state, batch, steps: int):
    """1 + ``steps`` calls on one repeated batch, each closed by
    ``block_until_ready``.  Only the first call may build a program: a call
    after it that lowers anything means the step hands back a state with
    another signature than it was given, and pays its compile again.
    Returns (state, per-step mean losses, last ExchangeInfo, seconds of the
    first call, ms of each later call)."""
    import jax
    import numpy as np

    lowered = []
    count = lambda event, _secs, **kw: lowered.append(event)
    jax.monitoring.register_event_duration_secs_listener(count)
    try:
        losses_seen, times, programs = [], [], []
        for _ in range(1 + steps):
            before, t0 = len(lowered), time.perf_counter()
            state, losses, info = step_fn(state, batch)
            jax.block_until_ready((state, losses))
            times.append(time.perf_counter() - t0)
            programs.append(sum(
                e.endswith("jaxpr_to_mlir_module_duration")
                for e in lowered[before:]
            ))
            losses_seen.append(float(np.mean(np.asarray(losses, np.float32))))
    finally:
        jax.monitoring.unregister_event_duration_listener(count)
    assert programs[0] >= 1 and not any(programs[1:]), (
        f"programs lowered per call: {programs}"
    )
    return state, losses_seen, info, times[0], [1e3 * t for t in times[1:]]


def check_train(state, losses, info, platform: str, n: int, decreasing: bool):
    import jax
    import numpy as np

    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    if decreasing:
        assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    partner = np.asarray(info.partner)
    assert np.array_equal(partner[partner], np.arange(n)), partner
    placed = {
        d.platform for leaf in jax.tree.leaves(state.params)
        for d in leaf.devices()
    }
    assert placed == {platform}, f"params on {placed}, expected {platform}"
    return partner.tolist()


def check_one_peer_per_device(params, n: int) -> None:
    import jax

    for leaf in jax.tree.leaves(params):
        shards = leaf.addressable_shards
        assert len(shards) == n and all(
            s.data.shape[0] == 1 for s in shards
        ), f"leaf {leaf.shape} is not one peer per shard"
        assert len({s.device for s in shards}) == n, (
            f"leaf {leaf.shape}: {n} peers on "
            f"{len({s.device for s in shards})} devices"
        )


def train_report(losses, partner, first_s, step_ms, **extra) -> dict:
    return dict(
        compile_s=round(first_s, 2),
        step_ms_median=round(statistics.median(step_ms), 3),
        step_ms=[round(t, 2) for t in step_ms],
        loss_first=round(losses[0], 5), loss_last=round(losses[-1], 5),
        partner=partner, **extra,
    )


# ---------------------------------------------------------------------------
# ResNet-50: stacked on one chip, ppermute over the mesh
# ---------------------------------------------------------------------------


def resnet_leg(sz: Sizes, transport_kind: str, n: int, platform: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.models.resnet import ImageNetResNet
    from dpwa_tpu.train import init_params_per_peer
    from dpwa_tpu.utils.launch import build_transport

    t0 = time.perf_counter()
    # Random pairs from a pool of 32, as examples/imagenet/main.py.
    cfg = make_local_config(n, schedule="random", pool_size=32)
    bundle = build_transport(cfg, transport_kind, "native")
    model = ImageNetResNet(
        stage_sizes=sz.resnet_stages, dtype=sz.resnet_dtype
    )
    S, B = sz.image, sz.resnet_batch
    stacked = init_params_per_peer(
        lambda k: model.init(k, jnp.zeros((1, S, S, 3))),
        jax.random.key(SEED), n,
    )
    opt = optax.sgd(RESNET_LR, momentum=0.9)
    state = bundle.init_state(stacked, opt, bundle.transport)
    del stacked

    def loss_fn(params, batch):
        x, y = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(params, x), y
        ).mean()

    step_fn = bundle.make_step(loss_fn, opt, bundle.transport)
    rng = np.random.default_rng(SEED)
    batch = jax.device_put(
        (
            rng.random((n, B, S, S, 3), np.float32),
            rng.integers(0, 1000, (n, B)).astype(np.int32),
        ),
        bundle.batch_sharding,
    )
    text = hlo_text(step_fn, state, batch) if transport_kind == "ici" else ""
    jax.block_until_ready((state, batch))
    setup_s = time.perf_counter() - t0
    state, losses, info, first_s, step_ms = run_steps(
        step_fn, state, batch, sz.steps
    )
    partner = check_train(state, losses, info, platform, n, decreasing=True)
    if transport_kind == "ici":
        assert "collective-permute" in text, "no collective-permute lowered"
        check_one_peer_per_device(state.params, n)
    return train_report(
        losses, partner, first_s, step_ms, setup_s=round(setup_s, 1),
        peers=n, batch_per_peer=B, image=S,
        pool_size=bundle.transport.schedule.pool_size,
    )


# ---------------------------------------------------------------------------
# The exchange alone: ICI against its stacked twin
# ---------------------------------------------------------------------------


def ici_exchange_leg(sz: Sizes, n: int, platform: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.interpolation import PeerMeta
    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.parallel.mesh import make_mesh, peer_sharding
    from dpwa_tpu.parallel.stacked import StackedTransport

    # A small tree of many leaves, replica i holding the value i.
    shapes = [(3,), (128,), (5, 7), (16, 128), (2, 3, 4), (1000,)] * 4
    peer_index = np.arange(n, dtype=np.float32)
    host_tree = {
        f"leaf{j:02d}": np.broadcast_to(
            peer_index.reshape((n,) + (1,) * len(s)), (n,) + s
        ).copy()
        for j, s in enumerate(shapes)
    }
    meta = PeerMeta(jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32))
    report, has_permute = {}, True
    for schedule in ("ring", "random"):
        for wire in ("f32", "bf16", "int8"):
            cfg = make_local_config(
                n, schedule=schedule, wire_dtype=wire, factor=0.5,
                **(dict(pool_size=4, seed=3) if schedule == "random" else {}),
            )
            ici = IciTransport(cfg, mesh=make_mesh(cfg))
            stk = StackedTransport(cfg)
            a = jax.device_put(host_tree, peer_sharding(ici.mesh))
            b = jax.device_put(host_tree)
            has_permute &= "collective-permute" in hlo_text(
                ici.exchange, a, meta, jnp.int32(0)
            )
            exact = (schedule, wire) == ("ring", "f32")
            worst = 0.0
            for step in range(4):
                before = jax.tree.map(np.asarray, a) if exact else None
                a, info_a = ici.exchange(a, meta, step)
                b, info_b = stk.exchange(b, meta, step)
                partner = np.asarray(info_a.partner)
                assert np.array_equal(partner, np.asarray(info_b.partner))
                assert np.array_equal(
                    np.asarray(info_a.participated),
                    np.asarray(info_b.participated),
                )
                np.testing.assert_allclose(
                    np.asarray(info_a.alpha), np.asarray(info_b.alpha),
                    rtol=1e-6,
                )
                for name, leaf in a.items():
                    got = np.asarray(leaf)
                    worst = max(
                        worst, float(np.max(np.abs(got - np.asarray(b[name]))))
                    )
                    if exact:
                        # Halves of small integers are exact in f32.
                        want = (before[name] + before[name][partner]) / 2
                        assert np.array_equal(got, want), (name, step)
            # tests/test_stacked.py demands rtol 1e-6 / atol 1e-7 of the two
            # on the CPU mesh; the values here are at most n.  An int8 code
            # may flip where the chip fuses the quantizer differently in
            # the two programs: that is reported, and bounded by the steps
            # of one code each round (alpha/127 of the largest value).
            cpu_tolerance = 1e-6 * n + 1e-7
            bound = 4 * 0.5 * n / 127 if wire == "int8" else cpu_tolerance
            assert worst <= bound, (
                f"{schedule}/{wire}: ICI off stacked by {worst}"
            )
            report[f"{schedule}/{wire}"] = dict(
                max_abs_diff=worst, as_on_cpu=worst <= cpu_tolerance
            )
            assert {d.platform for d in a["leaf00"].devices()} == {platform}
    assert has_permute, "an exchange lowered without collective-permute"
    return dict(
        peers=n, leaves=len(shapes), steps=4, ici_vs_stacked=report,
        ring_f32_exact=True,
    )


# ---------------------------------------------------------------------------
# The decoder: 1-D LoRA gossip, then peers x sp
# ---------------------------------------------------------------------------


def _llama_setup(sz: Sizes, n: int, **model_kwargs):
    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.models.llama import Llama, LlamaConfig, lora_optimizer
    from dpwa_tpu.train import init_params_per_peer

    base = dict(sz.llama, max_seq_len=4 * sz.seq_len, lora_rank=8)
    model = Llama(LlamaConfig(**base, **model_kwargs))
    # init runs outside shard_map, so it takes the model without sp_axis.
    stacked = init_params_per_peer(
        lambda k: Llama(LlamaConfig(**base)).init(
            k, jnp.zeros((1, 8), jnp.int32)
        ),
        jax.random.key(SEED + 1), n,
    )
    opt = lora_optimizer(
        optax.adam(1e-3), jax.tree.map(lambda v: v[0], stacked)
    )
    return model, stacked, opt


def _tokens(sz: Sizes, n: int, seq_len: int):
    import numpy as np

    toks = np.random.default_rng(SEED + 2).integers(
        0, sz.llama["vocab_size"], (n, sz.llama_batch, seq_len + 1)
    ).astype(np.int32)
    return toks[..., :-1], toks[..., 1:]


def ici_llama_leg(sz: Sizes, n: int, platform: str) -> dict:
    import jax
    import optax

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.models.llama import lora_filter
    from dpwa_tpu.utils.launch import build_transport

    t0 = time.perf_counter()
    # Rehearsal: the same library flash kernel, interpreted on the CPU.
    attn_impl = "flash" if sz.rehearsal else "auto"
    bundle = build_transport(
        make_local_config(n, schedule="ring"), "ici", "native"
    )
    model, stacked, opt = _llama_setup(sz, n, attn_impl=attn_impl)
    state = bundle.init_state(stacked, opt, bundle.transport)
    del stacked

    def loss_fn(params, batch):
        tokens, targets = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(params, tokens), targets
        ).mean()

    step_fn = bundle.make_step(
        loss_fn, opt, bundle.transport, exchange_filter=lora_filter
    )
    batch = jax.device_put(_tokens(sz, n, sz.seq_len), bundle.batch_sharding)
    with kernel_mode(sz):
        text = hlo_text(step_fn, state, batch)
        jax.block_until_ready((state, batch))
        setup_s = time.perf_counter() - t0
        state, losses, info, first_s, step_ms = run_steps(
            step_fn, state, batch, min(sz.steps, 3)
        )
    partner = check_train(state, losses, info, platform, n, decreasing=False)
    assert "collective-permute" in text, "no collective-permute lowered"
    # Interpreted kernels lower to plain HLO, so only the chip can show it.
    kernel = "tpu_custom_call" in text
    assert kernel or sz.rehearsal, "attention lowered to the einsum twin"
    return train_report(
        losses, partner, first_s, step_ms, setup_s=round(setup_s, 1),
        peers=n, seq_len=sz.seq_len, attn_impl=attn_impl,
        tpu_custom_call=kernel,
    )


def sp_llama_leg(sz: Sizes, platform: str) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.models.llama import lora_filter
    from dpwa_tpu.ops.zigzag_ring import zigzag_shard
    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.train_sp import (
        init_gossip_sp_state,
        make_gossip_sp_train_step,
        make_sp_mesh,
        sp_batch_sharding,
    )
    n, sp = 2, 2
    cfg = make_local_config(n, schedule="ring")
    mesh = make_sp_mesh(cfg, sp)
    transport = IciTransport(cfg, mesh=mesh)
    tokens = _tokens(sz, n, sp * sz.seq_len)
    variants = {
        "ring": dict(),
        "zigzag": dict(sp_layout="zigzag"),
        "a2a": dict(sp_strategy="a2a"),
    }
    out, first_losses = {}, {}
    for name, kwargs in variants.items():
        model, stacked, opt = _llama_setup(sz, n, sp_axis="sp", **kwargs)
        state = init_gossip_sp_state(stacked, opt, transport)
        del stacked

        def sp_loss(params, batch, model=model):
            x, y = batch
            losses = optax.softmax_cross_entropy_with_integer_labels(
                model.apply(params, x), y
            )
            return losses.sum(), jnp.float32(losses.size)

        step_fn = make_gossip_sp_train_step(
            sp_loss, opt, transport, exchange_filter=lora_filter
        )
        batch = tokens
        if name == "zigzag":
            batch = tuple(zigzag_shard(t, sp, axis=2) for t in tokens)
        batch = jax.device_put(batch, sp_batch_sharding(mesh))
        text = hlo_text(step_fn, state, batch)
        state, losses, info, first_s, step_ms = run_steps(
            step_fn, state, batch, min(sz.steps, 3)
        )
        partner = check_train(
            state, losses, info, platform, n, decreasing=False
        )
        assert "collective-permute" in text, f"{name}: no collective-permute"
        kernel = "tpu_custom_call" in text
        assert kernel or sz.rehearsal, f"{name}: attention is not the kernel"
        first_losses[name] = losses[0]
        out[name] = train_report(
            losses, partner, first_s, step_ms, tpu_custom_call=kernel
        )
        del state
    # One model, one batch, three exact attentions: equal to the dtype.
    tol = 1e-4 if sz.rehearsal else 2e-2
    for name, loss in first_losses.items():
        assert abs(loss - first_losses["ring"]) <= tol * abs(loss), (
            f"{name} loss {loss} vs ring {first_losses['ring']}"
        )
    return dict(
        peers=n, sp=sp, global_seq_len=sp * sz.seq_len, variants=out,
        partner=out["ring"]["partner"],
        compile_s=round(sum(v["compile_s"] for v in out.values()), 2),
        step_ms_median=out["ring"]["step_ms_median"],
        loss_first=out["ring"]["loss_first"],
        loss_last=out["ring"]["loss_last"],
    )


# ---------------------------------------------------------------------------
# The host path onto the device
# ---------------------------------------------------------------------------


def host_device_merge_leg(sz: Sizes, platform: str) -> dict:
    import jax
    import numpy as np

    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.device import handoff
    from dpwa_tpu.parallel.tcp import TcpTransport

    d = 100_000
    cfg = make_local_config(
        2, base_port=0, schedule="ring", fetch_probability=1.0,
        interpolation="constant", factor=0.3, rx_server="reactor",
    )
    rng = np.random.default_rng(SEED + 5)
    handoff.reset_handoff_stats()
    nodes = [TcpTransport(cfg, f"node{i}") for i in range(2)]
    try:
        for t in nodes:
            for i, other in enumerate(nodes):
                t.set_peer_port(i, other.port)
        mine = rng.standard_normal(d).astype(np.float32)
        replica = jax.device_put(mine)
        worst = 0.0
        for step in (0, 2, 4):  # the ring phases that pair node0 with node1
            theirs = rng.standard_normal(d).astype(np.float32)
            nodes[1].publish(theirs, 1.0, 0.5)
            replica, alpha, partner = nodes[0].exchange_on_device(
                replica, 1.0, 0.5, step
            )
            assert partner == 1 and alpha != 0.0, (partner, alpha)
            assert {dev.platform for dev in replica.devices()} == {platform}
            mine = ((1.0 - alpha) * mine + alpha * theirs).astype(np.float32)
            err = np.max(np.abs(np.asarray(replica) - mine))
            worst = max(worst, float(err))
        assert worst <= 1e-5, f"device merge off the numpy lerp by {worst}"
    finally:
        for t in nodes:
            t.close()
    return dict(
        d=d, rounds=3, max_abs_err=worst, handoff=handoff.handoff_stats()
    )


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="toy shapes on an emulated 8-device CPU mesh with interpreted "
        "kernels; says nothing about the chip",
    )
    ap.add_argument(
        "--legs", nargs="*", metavar="LEG",
        help="run only these legs (debugging; the proof is all of them)",
    )
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        # Both are read at first backend init, before anything imports jax.
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count=8".strip()
            )

    import jax

    devices = jax.devices()
    platform, chips = devices[0].platform, len(devices)
    device = dict(
        platform=platform, kind=devices[0].device_kind, count=chips
    )
    if platform != "tpu" and not args.rehearse_cpu:
        print(
            f"chip_smoke: jax found {device}, not a TPU; nothing was run",
            file=sys.stderr,
        )
        return EXIT_NO_ACCELERATOR

    from dpwa_tpu.utils.launch import enable_compile_cache

    cache_dir = enable_compile_cache()
    count_cache = lambda: (
        len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    )
    cache_before = count_cache()
    sz = Sizes(args.rehearse_cpu)
    mesh_n = min(chips, 4)
    legs = [
        # (name, chips needed, body)
        ("stacked_resnet50", 1,
         lambda: resnet_leg(sz, "stacked", 8, platform)),
        ("host_device_merge", 1, lambda: host_device_merge_leg(sz, platform)),
        ("ici_exchange", 2, lambda: ici_exchange_leg(sz, mesh_n, platform)),
        ("ici_resnet50", 2, lambda: resnet_leg(sz, "ici", mesh_n, platform)),
        ("ici_llama_flash", 2, lambda: ici_llama_leg(sz, mesh_n, platform)),
        ("sp_llama", 4, lambda: sp_llama_leg(sz, platform)),
    ]
    known = {name for name, _, _ in legs}
    if args.legs and not set(args.legs) <= known:
        ap.error(f"unknown leg(s) {sorted(set(args.legs) - known)}")

    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    log_path = os.path.join(
        HERE, "chiprun_out", f"chip_smoke_{platform}{chips}.jsonl"
    )
    ok = True
    with open(log_path, "w", encoding="utf-8") as log:

        def emit(record: dict, detail: str = "") -> None:
            print(json.dumps(record), flush=True)
            log.write(json.dumps(dict(record, detail=detail)) + "\n")
            log.flush()

        for name, need, body in legs:
            if args.legs and name not in args.legs:
                continue
            head = dict(
                leg=name, platform=platform, device_kind=device["kind"],
                device_count=chips,
            )
            if chips < need:
                emit(dict(
                    head, status="skipped",
                    reason=f"needs {need} chips, host has {chips}",
                ))
                continue
            t0 = time.perf_counter()
            try:
                result = body()
            except Exception as e:  # a leg's failure must not hide the rest
                ok = False
                detail = traceback.format_exc()
                print(detail, file=sys.stderr, flush=True)
                emit(
                    dict(
                        head, status="failed",
                        error=f"{type(e).__name__}: {e}"[:2000],
                        wall_s=round(time.perf_counter() - t0, 1),
                    ),
                    detail,
                )
                continue
            emit(dict(
                head, status="ok",
                wall_s=round(time.perf_counter() - t0, 1), **result,
            ))
        emit(dict(
            leg="compile_cache", dir=cache_dir,
            entries_before=cache_before, entries_after=count_cache(),
        ))
    summary = dict(ok=ok, device=device)
    if args.rehearse_cpu:
        summary["rehearsal"] = True
    print(json.dumps(summary), flush=True)
    return 0 if ok else EXIT_FAILED


if __name__ == "__main__":
    sys.exit(main())
