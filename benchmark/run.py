#!/usr/bin/env python3
"""One run of one benchmark cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no child.  The cell, its configuration, its traffic and its
per-layer metrics are all found by the names in ``BENCHMARK.json``: this file
holds no list of cells and knows a model family only through its builder
(``benchmark/builders/<family>.py``).  Set-up (state, a pool of batches made
on the device from the seed, the cell's one step program compiled and warmed)
is timed as ``setup_s``; then steps run in blocks, each closed by
``block_until_ready``, for ``--seconds``; then, untimed, the run is checked
against ``benchmark/reference.py`` and the last line of stdout is the result.

Without ``--rehearse-cpu`` the run exits non-zero, with no result line,
unless JAX's first device is a TPU and there are as many as the cell needs.
``--rehearse-cpu`` runs the builder's toy shapes on whatever JAX has and
reports counts only: every metric that is a time, a rate or a share of the
device is ``null``.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import NamedTuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_NO_ACCELERATOR = 4
# In a rehearsal only counts are reported: per-layer metrics whose source is
# program_counter, and of the end-to-end metrics the loss.
REHEARSAL_KEEPS = {"loss_at_k"}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class CompileLog:
    """Counts what JAX lowers and compiles, and what the persistent cache
    found: a program lowered inside the window is a failed run."""

    def __init__(self):
        import jax

        self.lowered = 0
        self.cache_misses = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _seconds, **_kw):
        if event.endswith("jaxpr_to_mlir_module_duration"):
            self.lowered += 1

    def _event(self, event, **_kw):
        if event.endswith("compilation_cache/cache_misses"):
            self.cache_misses += 1
        elif event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1


def shape_of(tree):
    import jax
    from jax.sharding import NamedSharding

    def spec(x):
        sh = getattr(x, "sharding", None)
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=sh if isinstance(sh, NamedSharding) else None,
        )

    return jax.tree.map(spec, tree)


class Cell(NamedTuple):
    """What a run is asked to do, read from BENCHMARK.json and the files it
    names (shrunk by the builder for a rehearsal)."""

    name: str
    chips: int
    config: dict  # the configuration file
    traffic: dict  # the cell's file under benchmark/workloads/
    metrics: dict  # {"end_to_end": [...], "per_layer": [...]} for this cell
    builder: object  # benchmark/builders/<family>.py
    rehearsal: bool


class Live(NamedTuple):
    """What set-up leaves for the window and the check."""

    built: object
    bundle: object
    optimizer: object
    state: object
    step_fn: object
    pool: list  # peer-stacked batches on the device
    losses: list  # one device array [n] per optimizer step since init
    frozen_at_init: list  # checksums of the leaves outside the exchange
    checksum: object
    record: dict  # host-side facts for the per-layer readers


def load_cell(name: str, rehearsal: bool) -> Cell:
    """Everything of a cell, found by the names in BENCHMARK.json."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    entry = cells[name]
    config_entry = next(
        c for c in manifest["configs"] if c["name"] == entry["config"]
    )
    metrics = {
        kind: [m for m in manifest[kind] if name in m.get("workloads", [name])]
        for kind in ("end_to_end", "per_layer")
    }
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = load_json(
        os.path.join(HERE, "workloads", entry["traffic"] + ".json")
    )
    builder = importlib.import_module(f"benchmark.builders.{config['family']}")
    if rehearsal:
        config, traffic = builder.rehearse(config, traffic)
        traffic = dict(traffic, block_steps=2, k=4, warmup_steps=2,
                       pool_batches=3, trace_blocks=1)
    if traffic["k"] % traffic["block_steps"] or traffic["warmup_steps"] < 2:
        raise SystemExit(
            "a cell's k is a multiple of its block_steps, and it warms up "
            "with at least 2 steps"
        )
    return Cell(name, entry["chips"], config, traffic, metrics, builder,
                rehearsal)


def set_up(cell: Cell, seed: int, log: CompileLog) -> Live:
    """State, batch pool and step program, all from the seed; the step warmed
    with the cell's ``warmup_steps``."""
    import jax

    from benchmark import reference, traffic
    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.train import init_params_per_peer
    from dpwa_tpu.utils.launch import build_transport

    t0 = time.perf_counter()
    spec = cell.traffic
    n, b = spec["peers"], spec["per_peer_batch"]
    built = cell.builder.build(cell.config, spec)
    protocol = {
        key: spec[key] for key in ("pool_size", "group_size", "inter_period")
        if spec.get(key) is not None
    }
    # The schedule's seed is the cell's, not the run's: the pairing pool is a
    # constant of the compiled step, and a new one would compile again.
    cfg = make_local_config(
        n, schedule=spec["schedule"], seed=spec["schedule_seed"],
        fetch_probability=spec["fetch_probability"],
        interpolation=spec["interpolation"], factor=spec["factor"],
        wire_dtype=spec["wire_dtype"], **protocol,
    )
    bundle = build_transport(cfg, spec["transport"], "native")
    if cell.rehearsal:
        # A rehearsal times nothing, and XLA:CPU's cached programs are tied
        # to the machine that compiled them.
        jax.config.update("jax_enable_compilation_cache", False)
    key = jax.random.key(seed)
    stacked = init_params_per_peer(built.init_fn, jax.random.fold_in(key, 0), n)
    optimizer = built.make_optimizer(
        jax.eval_shape(built.init_fn, jax.random.key(0))
    )
    state = bundle.init_state(stacked, optimizer, bundle.transport)
    del stacked
    exchanged, frozen = reference.partition(state.params, built.exchange_filter)
    checksum = jax.jit(
        lambda p: reference.frozen_checksum(p, built.exchange_filter)
    )
    frozen_at_init = checksum(state.params) if frozen else []
    generate = traffic.make_generator(
        spec["task"], built.batch_shape, n, b, bundle.batch_sharding
    )
    data_key = jax.random.fold_in(key, 1)
    pool = [generate(data_key, i) for i in range(spec["pool_batches"])]
    step_fn = bundle.make_step(
        built.loss_fn, optimizer, bundle.transport,
        exchange_filter=built.exchange_filter, overlap=spec["overlap"],
    )
    jax.block_until_ready((state, pool))
    state_setup_s = time.perf_counter() - t0

    losses, warm = [], []
    for i in range(spec["warmup_steps"]):
        t0 = time.perf_counter()
        state, step_losses, _ = step_fn(state, pool[i % len(pool)])
        jax.block_until_ready((state, step_losses))
        warm.append(time.perf_counter() - t0)
        losses.append(step_losses)
    record = dict(
        cell=spec, device_kind=jax.devices()[0].device_kind,
        state_setup_s=state_setup_s,
        compile_s=max(0.0, warm[0] - min(warm[1:])),
        cache_hit=log.cache_misses == 0, block_steps=spec["block_steps"],
        leaf_sizes=[leaf.size // n for _, leaf in exchanged],
        flops_per_sample=built.flops_per_sample, kernel_work=built.kernel_work,
    )
    return Live(built, bundle, optimizer, state, step_fn, pool, losses,
                frozen_at_init, checksum, record)


def run_window(cell: Cell, live: Live, seconds: float, trace_dir, log):
    """Steps in blocks, each closed by ``block_until_ready``, until
    ``seconds`` have passed; the block in flight is finished and counted.
    With ``trace_dir`` the blocks after the first are traced, ``trace_blocks``
    of them; a traced block trains, and is kept out of the rates.  Returns the
    state and ``(blocks, traced blocks, per-call dispatch ms)``, a block being
    (seconds, programs lowered inside it)."""
    import jax

    annotate = jax.profiler.TraceAnnotation
    state, pool, losses = live.state, live.pool, live.losses
    block_steps = cell.traffic["block_steps"]
    tracing, traced, blocks, dispatch_ms = False, 0, [], []
    t_window = time.perf_counter()
    while time.perf_counter() - t_window < seconds:
        if trace_dir and len(blocks) == 1 and not traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            tracing = True
        lowered, t0 = log.lowered, time.perf_counter()
        for _ in range(block_steps):
            with annotate("bench.batch_pick"):
                batch = pool[len(losses) % len(pool)]
            t_call = time.perf_counter()
            with annotate("bench.step_call"):
                state, step_losses, _ = live.step_fn(state, batch)
            dispatch_ms.append(1e3 * (time.perf_counter() - t_call))
            losses.append(step_losses)
        with annotate("bench.block_sync"):
            jax.block_until_ready((state, step_losses))
        block = (time.perf_counter() - t0, log.lowered - lowered)
        if not tracing:
            blocks.append(block)
            continue
        traced += 1
        if block[1]:
            blocks.append(block)  # a compilation is counted wherever it fell
        if traced >= cell.traffic["trace_blocks"]:
            jax.profiler.stop_trace()
            tracing = False
    if tracing:
        jax.profiler.stop_trace()
    return state, (blocks, traced, dispatch_ms)


def check(cell: Cell, live: Live, state, lowered_in_window: int, loss_at_k):
    """Everything that decides ``correct``; returns the state after the one
    checked step, the reasons (empty when correct) and what to print."""
    import jax
    import numpy as np

    from benchmark import reference

    spec, built = cell.traffic, live.built
    batch = live.pool[len(live.losses) % len(live.pool)]
    # 1. The step is a local update, a permutation and a lerp.  The
    #    reference's local update runs first, and only its exchanged leaves
    #    are kept while the system's step consumes the state.
    local = reference.make_local_update(
        built.loss_fn, live.optimizer, built.exchange_filter
    )
    u_leaves, moved = local(state.params, state.opt_state, batch)
    jax.block_until_ready(u_leaves)
    state, _, info = live.step_fn(state, batch)
    reasons = reference.check_info(
        info.partner, info.alpha, info.participated, spec["factor"]
    )
    verdict = reference.Verdict(False, (), 0.0, 0.0)
    if not reasons:
        merged = reference.merge(u_leaves, info.partner, info.alpha)
        verdict = reference.compare(
            state.params, merged, moved, info.alpha, built.exchange_filter
        )
        reasons += list(verdict.reasons)
        del merged
    del u_leaves
    # The exchange body alone, on the live tree: no gradient stands between
    # the two sides here, so the merge is held to float32 rounding and any
    # coarser wire is far outside, in every cell.
    merged, alone = exchange_alone(live.bundle.transport, state, built.exchange_filter)
    alone_reasons = reference.check_info(
        alone.partner, alone.alpha, alone.participated, spec["factor"]
    )
    wire = reference.Verdict(False, (), 0.0, 0.0)
    if not alone_reasons:
        before = [v for _, v in reference.partition(
            state.params, built.exchange_filter)[0]]
        wire = reference.compare(
            merged, reference.merge(before, alone.partner, alone.alpha),
            np.zeros(len(before)), alone.alpha, None,
        )
        alone_reasons = list(wire.reasons)
    reasons += [f"exchange alone: {r}" for r in alone_reasons]
    del merged
    if live.frozen_at_init and any(
        int(a) != int(c)
        for a, c in zip(live.frozen_at_init, live.checksum(state.params))
    ):
        reasons.append("a frozen leaf changed since init")
    # 2. The model is the configuration's plain reference.
    model_error, model_size = map(float, reference.make_model_check(
        built.apply_fn, built.reference_forward, built.reference_inputs
    )(state.params, batch))
    if not model_error <= reference.MODEL_TOLERANCE * model_size:
        reasons.append(
            f"logits off the plain reference by {model_error:.3e} rms "
            f"of {model_size:.3e}"
        )
    # 3. The loss learned.
    ceiling = spec["loss_ceiling"]
    if not np.isfinite(loss_at_k):
        reasons.append(f"loss_at_k is {loss_at_k}")
    elif ceiling is not None and not cell.rehearsal and loss_at_k > ceiling:
        reasons.append(f"loss_at_k {loss_at_k:.4f} above ceiling {ceiling}")
    # 4. Nothing compiled in the window, and the lowered step holds what the
    #    cell says it must (the kernel dispatchers give way silently).
    if lowered_in_window:
        reasons.append(f"{lowered_in_window} program(s) lowered in the window")
    expect = [
        m for m in spec["expect_hlo"]
        if not (cell.rehearsal and m == "tpu_custom_call")
    ]
    if expect:
        text = jax.jit(live.step_fn).lower(
            shape_of(state), shape_of(batch)
        ).as_text(dialect="hlo")
        reasons += [f"lowered step has no {m}" for m in expect if m not in text]
    said = dict(
        reasons=reasons, worst_ratio=verdict.worst_ratio,
        wire_margin=verdict.wire_margin, alone_ratio=wire.worst_ratio,
        alone_wire_margin=wire.wire_margin,
        model_vs_reference=model_error / model_size if model_size else None,
        partner=np.asarray(info.partner).tolist(),
    )
    return state, reasons, said


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    cell = load_cell(args.workload, args.rehearse_cpu)
    flag = "--xla_force_host_platform_device_count"
    if cell.rehearsal and flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            f"{os.environ.get('XLA_FLAGS', '')} {flag}={cell.chips}".strip()
        )

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not cell.rehearsal and (platform != "tpu" or len(devices) < cell.chips):
        print(
            f"{cell.name} needs {cell.chips} TPU chip(s); JAX has "
            f"{len(devices)} {platform} device(s)", file=sys.stderr,
        )
        return EXIT_NO_ACCELERATOR
    # Every program, however small, goes to the persistent cache, so that
    # only a checkout's first run of a cell compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    import jax.numpy as jnp
    import numpy as np

    from benchmark import flops, tracered

    say = lambda **kw: print(json.dumps(kw), flush=True)
    log = CompileLog()
    spec = cell.traffic
    block_steps, k_step = spec["block_steps"], spec["k"]
    per_step = spec["peers"] * spec["per_peer_batch"]

    live = set_up(cell, args.seed, log)
    record = live.record
    setup_s = time.perf_counter() - _T0
    say(phase="setup", setup_s=setup_s, cache_hits=log.cache_hits,
        cache_misses=log.cache_misses, **{
            key: record[key]
            for key in ("state_setup_s", "compile_s", "cache_hit")
        })

    trace_dir = os.path.join(
        HERE, "out", "trace", f"{cell.name}-{args.seed}"
    ) if args.trace else None
    lowered = log.lowered
    state, (blocks, traced, dispatch_ms) = run_window(
        cell, live, args.seconds, trace_dir, log
    )
    lowered_in_window = log.lowered - lowered

    # After the window, untimed.
    memory_peak = peak_device_bytes(devices[:cell.chips])
    losses = live.losses
    window_steps = len(losses) - spec["warmup_steps"]
    while len(losses) < k_step:  # the same point of the same trajectory
        state, step_losses, _ = live.step_fn(state, live.pool[len(losses) % len(live.pool)])
        losses.append(step_losses)
    all_losses = np.asarray(jnp.stack(losses), np.float64)  # [steps, peers]
    in_window = all_losses[spec["warmup_steps"]:][:window_steps]
    failed = int(np.sum(~np.all(np.isfinite(in_window), axis=1)))
    failed += sum(block_steps for _, low in blocks if low)
    # Mean over the peers and over the cell's last loss_steps steps up to K.
    loss_steps = spec.get("loss_steps", block_steps)
    loss_at_k = float(np.mean(all_losses[max(0, k_step - loss_steps):k_step]))
    clean = [s for s, low in blocks if not low]
    record.update(
        blocks=clean, dispatch_ms=dispatch_ms, traced_steps=traced * block_steps
    )
    if args.trace:
        record["exchange_alone_ms"] = time_exchange_alone(
            live.bundle.transport, state, live.built.exchange_filter
        )
    state, reasons, said = check(cell, live, state, lowered_in_window, loss_at_k)
    say(phase="check", loss_first=float(np.mean(all_losses[0])),
        loss_at_k=loss_at_k, steps=len(losses), blocks=len(blocks),
        loss_curve=all_losses.mean(1).round(4).tolist(), **said)

    # The result.
    samples_per_s = (
        len(clean) * block_steps * per_step / sum(clean) if clean else None
    )
    values = dict(
        setup_s=setup_s, samples_per_s=samples_per_s, loss_at_k=loss_at_k,
        peak_hbm_gb=memory_peak / 1e9 if memory_peak is not None else None,
    )
    if samples_per_s and not cell.rehearsal:
        peak = flops.peak(devices[0].device_kind)["bf16_flops_per_s"]
        values["mfu"] = 100.0 * live.built.flops_per_sample * samples_per_s / (
            cell.chips * peak
        )
    device = dict(
        platform=platform, kind=devices[0].device_kind, count=len(devices),
        memory_peak_bytes=memory_peak,
    )
    result = dict(
        correct=not reasons, attempted=window_steps, failed=failed,
        metrics={}, device=device,
    )
    if args.trace:
        found = [
            os.path.join(d, f) for d, _, files in os.walk(trace_dir)
            for f in files if f.endswith(".xplane.pb")
        ]
        trace = tracered.load(found[0]) if found else None
        for m in cell.metrics["per_layer"]:
            reader = importlib.import_module(
                f"benchmark.layer_metrics.{m['name']}"
            )
            value = reader.reduce(trace, record)
            if value is not None:
                values[m["name"]] = value
        if trace is not None and trace.device_ops:
            if not cell.rehearsal:
                busy = tracered.busy_seconds(trace)
                device["busy_s"] = sum(busy.values()) / len(busy)
                device["window_s"] = trace.window[1] - trace.window[0]
            result["breakdown"] = dict(
                device_ops=tracered.top_ops(trace, 10),
                idle_gaps=tracered.idle_gaps(trace, 5),
            )
    for m in cell.metrics["per_layer" if args.trace else "end_to_end"]:
        value = values.get(m["name"])
        counted = m["source"] == "program_counter" or m["name"] in REHEARSAL_KEEPS
        if cell.rehearsal and not counted:
            value = None  # a CPU run gives no device number
        elif value is None:
            continue
        result["metrics"][m["name"]] = dict(value=value, unit=m["unit"])
    print(json.dumps(result), flush=True)
    return 0


def peak_device_bytes(devices):
    """The most a chip held, on the fullest chip; None where the backend does
    not say.  The TPU runtime counts a loaded program's temporaries under
    ``bytes_reserved`` and not under ``bytes_in_use`` (PERF.md section 2), so
    the peak is the larger of the live arrays' own peak and what is live plus
    reserved now, when the window closes with the step program loaded."""
    peaks = []
    for device in devices:
        stats = device.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        peaks.append(max(
            stats["peak_bytes_in_use"],
            stats["bytes_in_use"] + stats.get("bytes_reserved", 0),
        ))
    return max(peaks)


def exchange_alone(transport, state, exchange_filter, step=None):
    """``transport.exchange`` on the live tree's exchanged leaves, outside any
    step: ``(merged tree, ExchangeInfo)``."""
    from dpwa_tpu.interpolation import PeerMeta
    from dpwa_tpu.utils.pytree import partition

    tree = state.params
    if exchange_filter is not None:
        tree, _ = partition(tree, exchange_filter)
    meta = PeerMeta(state.clock, state.loss)
    return transport.exchange(tree, meta, state.step if step is None else step)


def time_exchange_alone(transport, state, exchange_filter, calls: int = 20):
    """``calls`` rounds of :func:`exchange_alone` closed by one
    ``block_until_ready``, in ms a round.  Times the layer from outside; it
    goes when the program's step has an exchange span."""
    import jax

    jax.block_until_ready(exchange_alone(transport, state, exchange_filter, 0))
    t0 = time.perf_counter()
    for i in range(calls):  # one result alive at a time
        out = exchange_alone(transport, state, exchange_filter, 1 + i)[0]
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / calls


if __name__ == "__main__":
    sys.exit(main())
