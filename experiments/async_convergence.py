"""Free-running async TCP gossip vs SPMD masked emulation — convergence study.

SURVEY.md §7 hard part #1: the reference's peers are truly asynchronous
(independent processes, probabilistic fetches, drifting clocks); the SPMD
rebuild *emulates* that with a deterministic per-step pairing plus a masked
merge.  The lock-step bit-parity test (tests/test_parity.py) proves the easy
half.  This experiment closes the hard half: it runs

- ``tcp``   — 8 FREE-RUNNING OS processes gossiping over real sockets, no
  lock-step driver, random pull schedule, ``fetch_probability = 0.5``, with
  per-step timing jitter so local clocks genuinely drift;
- ``ici``   — the SPMD masked emulation of the same protocol on a forced
  8-device CPU mesh (one jitted program, ppermute exchange);
- ``stacked`` — the same emulation as a single-device stacked (vmapped) step;

on the same offline task (sklearn 8×8 digits, SmallNet, SGD+momentum, the
same per-peer data streams) across the same seeds, and records per-peer
loss/accuracy trajectories as JSONL under ``artifacts/async_convergence/``.
``analyze`` reduces them to a summary (final accuracy, steps-to-90%,
trajectory deviation between modes).  This doubles as the
steps-to-target-accuracy artifact on real data (BASELINE.json metric) until
a full CIFAR-10 is mountable offline.

Usage::

    python experiments/async_convergence.py run            # everything
    python experiments/async_convergence.py run --seeds 0 --modes tcp
    python experiments/async_convergence.py analyze        # re-summarize
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Variant runs (e.g. the bf16-wire validation, the ResNet-20 benchmark
# task) redirect artifacts and set the wire dtype / task through the
# environment so every spawned leg inherits them; the committed default
# study uses f32 + SmallNet + the default dir.
WIRE_DTYPE = os.environ.get("DPWA_EXP_WIRE_DTYPE", "f32")
# Task: "smallnet" (8x8 digits, fast sanity substrate) or "resnet20" —
# the BASELINE.json:8 benchmark model on the best offline stand-in for
# CIFAR-10 (the digits upscaled to 32x32 RGB; same classes, real images,
# a real train/test generalization gap).
TASK = os.environ.get("DPWA_EXP_TASK", "smallnet")
ART_DIR = os.environ.get(
    "DPWA_EXP_ART_DIR",
    os.path.join(REPO_ROOT, "artifacts", "async_convergence"),
)
if REPO_ROOT not in sys.path:  # direct-script invocation from anywhere
    sys.path.insert(0, REPO_ROOT)

N_PEERS = 8
BATCH = 32
LR = 0.05
MOMENTUM = 0.9
STEPS = 400
EVAL_EVERY = 20
FETCH_P = 0.5
POOL_SIZE = 16
DATA_SEED = 0  # train/test split is fixed; per-run seed varies streams+init
JITTER_MS = 2.0  # uniform per-step sleep in the tcp workers: forces drift


def experiment_config(seed: int, base_port: int = 0):
    """One config drives all three transports (the BASELINE.json:5 contract).

    Reference-style fully-async knobs: random schedule, one-sided pull mode
    (each peer independently pulls a partner — SURVEY.md §3.2), fetch
    probability 0.5."""
    from dpwa_tpu.config import make_local_config

    return make_local_config(
        N_PEERS,
        schedule="random",
        fetch_probability=FETCH_P,
        seed=seed,
        mode="pull",
        pool_size=POOL_SIZE,
        base_port=base_port,
        timeout_ms=2000,
        wire_dtype=WIRE_DTYPE,
    )


def _jsonl_path(mode: str, seed: int) -> str:
    return os.path.join(ART_DIR, f"run_{mode}_s{seed}.jsonl")


def _cifar_shaped_digits(seed: int):
    """Digits upscaled to 32x32x3 — the offline CIFAR-10 stand-in.

    Nearest-neighbor 4x upsample + channel tile: real images, 10 classes,
    CIFAR's exact input shape, and a real generalization gap; the closest
    substrate this zero-egress box can offer the BASELINE.json:8 task."""
    import numpy as np

    from dpwa_tpu.data import load_digits_dataset

    x_tr, y_tr, x_te, y_te = load_digits_dataset(seed=seed)

    def up(x):
        x = np.repeat(np.repeat(x, 4, axis=1), 4, axis=2)  # 8x8 -> 32x32
        return np.tile(x, (1, 1, 1, 3)).astype(np.float32)

    return up(x_tr), y_tr, up(x_te), y_te


def _setup_task(seed: int):
    """(model, stacked init params fn, batches iterator, test set, loss)."""
    import jax
    import jax.numpy as jnp
    import optax

    from dpwa_tpu.data import load_digits_dataset, peer_batches

    if TASK == "resnet20":
        from dpwa_tpu.models.resnet import ResNet20

        x_tr, y_tr, x_te, y_te = _cifar_shaped_digits(DATA_SEED)
        # Standardize (CIFAR-style preprocessing) and use Adam: SGD(0.05)
        # leaves this 20-layer GroupNorm net at chance for hundreds of
        # steps on 1.4k samples; Adam(1e-3) reaches >95% by ~step 200
        # (single-replica probe).  The gossip protocol under study is
        # optimizer-agnostic.
        mu, sd = x_tr.mean(), x_tr.std()
        x_tr, x_te = (x_tr - mu) / sd, (x_te - mu) / sd
        model = ResNet20()  # GroupNorm: pure params, all transports
        shape = (1, 32, 32, 3)
        opt = optax.adam(1e-3)
    else:
        from dpwa_tpu.models.mnist import SmallNet

        x_tr, y_tr, x_te, y_te = load_digits_dataset(seed=DATA_SEED)
        model = SmallNet()
        shape = (1, 8, 8, 1)
        opt = optax.sgd(LR, momentum=MOMENTUM)
    params0 = model.init(jax.random.key(seed), jnp.zeros(shape))
    batches = peer_batches(x_tr, y_tr, N_PEERS, BATCH, seed=seed)

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply(params, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    return model, params0, opt, batches, (x_te, y_te), loss_fn


# ---------------------------------------------------------------- tcp worker


def tcp_worker(args) -> int:
    """One free-running peer process: local SGD + socket gossip, own pace."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_platforms", "cpu")

    from dpwa_tpu.parallel.tcp import TcpTransport
    from dpwa_tpu.utils.pytree import ravel

    if args.device_resident and args.overlapped:
        raise SystemExit(
            "--device-resident and --overlapped are mutually exclusive "
            "modes (tcpdev vs tcpov)"
        )
    me, seed = args.peer, args.seed
    model, params, opt, batches, (x_te, y_te), loss_fn = _setup_task(seed)
    opt_state = opt.init(params)
    cfg = experiment_config(seed, base_port=args.base_port)
    transport = TcpTransport(cfg, f"node{me}")

    @jax.jit
    def local_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = opt.update(grads, opt_state, params)
        return jax.tree.map(
            lambda p, u: p + u, params, updates
        ), opt_state, loss

    @jax.jit
    def accuracy(params):
        logits = model.apply(params, x_te)
        return jnp.mean(jnp.argmax(logits, -1) == y_te)

    _, unravel = ravel(params)
    rng = np.random.default_rng(seed * 1000 + me)
    records = []
    clock = 0.0
    # Rendezvous: publish the initial weights (the Rx server serves nothing
    # until the first publish), then wait until every peer's Rx server
    # answers, so early workers don't burn their first fetches on peers
    # still compiling.
    transport.publish(np.asarray(ravel(params)[0], np.float32), clock, 0.0)
    deadline = time.time() + 60
    while time.time() < deadline:
        if all(
            transport.fetch(i, timeout_ms=200) is not None
            for i in range(N_PEERS)
            if i != me
        ):
            break
        time.sleep(0.1)

    if args.device_resident:
        mode_name = "tcpdev"
    elif args.overlapped:
        mode_name = "tcpov"
    else:
        mode_name = "tcp"
    prev_loss = 0.0
    for k in range(args.steps):
        stacked = next(batches)  # identical streams across modes
        batch = (stacked[0][me], stacked[1][me])
        if args.overlapped:
            # SPMD overlap=True semantics over sockets: publish the
            # PRE-step replica with the PREVIOUS step's loss, fetch the
            # partner WHILE the local step computes, then land the local
            # update on the merged result.
            pre = np.asarray(ravel(params)[0], np.float32)
            clock += 1.0
            ex = transport.exchange_overlapped_start(
                pre, clock, prev_loss, k
            )
            params_new, opt_state, loss = local_step(
                params, opt_state, batch
            )
            post = np.asarray(ravel(params_new)[0], np.float32)
            merged, alpha, partner = ex.finish(pre, post - pre)
            params = unravel(jnp.asarray(merged))
            prev_loss = float(loss)
        else:
            params, opt_state, loss = local_step(params, opt_state, batch)
            clock += 1.0
        if args.device_resident:
            # VERDICT r3 #6: the replica never exists as host state — the
            # flat vector stays a JAX device array, the merge is a jitted
            # on-device lerp, and TCP touches only the wire staging
            # copies (publish download / fetched-partner upload).
            flat = ravel(params)[0]
            merged, alpha, partner = transport.exchange_on_device(
                flat, clock, float(loss), k
            )
            if alpha != 0.0:
                params = unravel(merged)
        elif args.overlapped:
            pass  # whole round already handled ABOVE, around local_step
        else:
            vec = np.asarray(ravel(params)[0], np.float32)
            merged, alpha, partner = transport.exchange(
                vec, clock, float(loss), k
            )
            if alpha != 0.0:
                params = unravel(jnp.asarray(merged))
        if k % EVAL_EVERY == 0 or k == args.steps - 1:
            records.append(
                {
                    "mode": mode_name,
                    "seed": seed,
                    "peer": me,
                    "step": k,
                    "clock": clock,
                    "loss": float(loss),
                    "acc": float(accuracy(params)),
                    "alpha": float(alpha),
                    "partner": int(partner),
                    "wire": WIRE_DTYPE,
                    "task": TASK,
                }
            )
        if JITTER_MS > 0:
            time.sleep(rng.uniform(0, JITTER_MS / 1000.0))

    with open(args.out, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    print(f"WORKER_DONE {me}", flush=True)
    # Keep serving the Rx thread for laggards, then exit.
    time.sleep(args.grace)
    transport.close()
    return 0


def run_tcp(
    seed: int, steps: int, device_resident: bool = False,
    overlapped: bool = False,
) -> None:
    """Spawn N free-running worker processes; merge their JSONL shards."""
    if device_resident:
        mode = "tcpdev"
    elif overlapped:
        mode = "tcpov"
    else:
        mode = "tcp"
    # Below the Linux ephemeral range (32768+): a transient outgoing
    # connection can never squat one of the workers' listening ports; the
    # device-resident variant gets its own block so both tcp legs of one
    # seed can ever overlap in a wrapper script without port fights.
    base_port = (
        17000 + seed * 20
        + (1000 if device_resident else 0)
        + (2000 if overlapped else 0)
    )
    os.makedirs(ART_DIR, exist_ok=True)
    shard_paths = [
        os.path.join(ART_DIR, f".{mode}_s{seed}_p{i}.jsonl")
        for i in range(N_PEERS)
    ]
    from dpwa_tpu.utils.launch import child_process_env

    env = child_process_env(REPO_ROOT)
    procs = [
        subprocess.Popen(
            [
                sys.executable,
                os.path.abspath(__file__),
                "worker",
                "--peer", str(i),
                "--seed", str(seed),
                "--steps", str(steps),
                "--base-port", str(base_port),
                "--out", shard_paths[i],
                "--grace", "20",
                *(["--device-resident"] if device_resident else []),
                *(["--overlapped"] if overlapped else []),
            ],
            env=env,
            cwd=REPO_ROOT,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(N_PEERS)
    ]
    # Workers exit on their own after steps + grace (the grace sleep keeps
    # each Rx server alive for laggards' fetches).  The wait is wall-clock
    # bounded so one hung worker aborts the leg instead of hanging the
    # whole multi-seed study; a dead or hung worker never leaks the others
    # (they hold the port range).
    # Rendezvous + jit startup + generous step time.  ResNet-20 on this
    # box's single CPU core costs ~0.3 s/peer-step with 8 workers
    # contending 8-way, vs ms for SmallNet.
    budget = 120 + steps * (6.0 if TASK == "resnet20" else 1.0)
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=max(30, budget))
            if "WORKER_DONE" not in out:
                raise RuntimeError(
                    f"tcp worker rc={p.returncode} without DONE:\n{out}"
                )
            outs.append(out)
    except subprocess.TimeoutExpired as e:
        raise RuntimeError(f"tcp worker hung past {budget:.0f}s") from e
    finally:
        for p in procs:
            p.kill()
        for p in procs:
            p.wait(timeout=30)
    with open(_jsonl_path(mode, seed), "w") as out:
        for sp in shard_paths:
            with open(sp) as f:
                out.write(f.read())
            os.remove(sp)
    print(f"{mode} seed={seed}: {len(outs)} workers done")


# ------------------------------------------------------------- spmd runners


def run_spmd(transport_kind: str, seed: int, steps: int) -> None:
    """The SPMD masked emulation: ici (8-dev CPU mesh) or stacked (1 dev)."""
    import numpy as np

    if transport_kind == "ici":
        from dpwa_tpu.utils.devices import ensure_devices

        ensure_devices(N_PEERS, mode="cpu")
    else:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")

    from dpwa_tpu.train import (
        make_gossip_eval_fn,
        stack_params,
    )

    model, params0, opt, batches, (x_te, y_te), loss_fn = _setup_task(seed)
    stacked = stack_params(params0, N_PEERS)
    cfg = experiment_config(seed)

    if transport_kind == "ici":
        from dpwa_tpu.parallel.ici import IciTransport
        from dpwa_tpu.parallel.mesh import make_mesh, peer_sharding
        from dpwa_tpu.train import init_gossip_state, make_gossip_train_step

        transport = IciTransport(cfg, mesh=make_mesh(cfg))
        state = init_gossip_state(stacked, opt, transport)
        step_fn = make_gossip_train_step(loss_fn, opt, transport)
        eval_fn = make_gossip_eval_fn(model.apply, transport)
        sharding = peer_sharding(transport.mesh)
    else:
        from dpwa_tpu.parallel.stacked import (
            StackedTransport,
            init_stacked_state,
            make_stacked_train_step,
        )

        transport = StackedTransport(cfg)
        state = init_stacked_state(stacked, opt, transport)
        step_fn = make_stacked_train_step(loss_fn, opt, transport)
        eval_fn = make_gossip_eval_fn(model.apply)
        sharding = None

    records = []
    for k in range(steps):
        bx, by = next(batches)
        batch = (
            jax.device_put(bx, sharding),
            jax.device_put(by, sharding),
        )
        state, losses, info = step_fn(state, batch)
        if k % EVAL_EVERY == 0 or k == steps - 1:
            accs = np.asarray(eval_fn(state.params, x_te, y_te))
            losses = np.asarray(losses)
            alphas = np.asarray(info.alpha)
            partners = np.asarray(info.partner)
            for i in range(N_PEERS):
                records.append(
                    {
                        "mode": transport_kind,
                        "seed": seed,
                        "peer": i,
                        "step": k,
                        "clock": float(k + 1),
                        "loss": float(losses[i]),
                        "acc": float(accs[i]),
                        "alpha": float(alphas[i]),
                        "partner": int(partners[i]),
                        "wire": WIRE_DTYPE,
                        "task": TASK,
                    }
                )
    os.makedirs(ART_DIR, exist_ok=True)
    with open(_jsonl_path(transport_kind, seed), "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    final = np.mean([r["acc"] for r in records if r["step"] == steps - 1])
    print(f"{transport_kind} seed={seed}: final mean acc {final:.4f}")


# ----------------------------------------------------------------- analysis


def analyze() -> dict:
    """Reduce the JSONL runs to the committed summary."""
    import numpy as np

    runs = {}  # (mode, seed) -> {step -> [accs]}
    wires = set()
    tasks = set()
    for name in sorted(os.listdir(ART_DIR)):
        if not name.startswith("run_") or not name.endswith(".jsonl"):
            continue
        with open(os.path.join(ART_DIR, name)) as f:
            for line in f:
                r = json.loads(line)
                key = (r["mode"], r["seed"])
                # Pre-field records were all produced with the f32 wire.
                wires.add(r.get("wire", "f32"))
                # Provenance from the RECORDS; records predating the task
                # field fall back to this process's TASK (env/flag).
                tasks.add(r.get("task", TASK))
                runs.setdefault(key, {}).setdefault(r["step"], []).append(
                    r["acc"]
                )

    def curve(mode, seed):
        steps = sorted(runs[(mode, seed)])
        return steps, [float(np.mean(runs[(mode, seed)][s])) for s in steps]

    modes = sorted({m for m, _ in runs})
    seeds = sorted({s for _, s in runs})
    # The step count the runs ACTUALLY used (curves end at steps-1), not
    # the module default, which a --steps override may differ from.  Runs
    # of different lengths in one artifact dir mean stale JSONL from an
    # earlier invocation is being compared against fresh curves — surface
    # that in the summary instead of silently averaging across lengths.
    per_run_steps = {
        f"{m}_s{s}": 1 + max(per) for (m, s), per in sorted(runs.items())
    }
    actual_steps = max(per_run_steps.values())
    mixed = len(set(per_run_steps.values())) > 1
    task_labels = {
        "resnet20": (
            "digits upscaled to 32x32x3 (CIFAR-shaped, standardized), "
            "ResNet-20 (GroupNorm), Adam(1e-3), batch 32"
        ),
        "smallnet": "sklearn digits 8x8, SmallNet, SGD(0.05, m=0.9), batch 32",
    }
    rec_task = sorted(tasks)[0] if len(tasks) == 1 else None
    summary = {
        "task": (
            task_labels.get(rec_task, rec_task)
            if rec_task is not None
            else f"MIXED tasks in one artifact dir: {sorted(tasks)}"
        ),
        "protocol": {
            "n_peers": N_PEERS,
            "schedule": "random",
            "mode": "pull",
            "fetch_probability": FETCH_P,
            "steps": actual_steps,
            "tcp_jitter_ms": JITTER_MS,
            # Provenance comes from the RECORDS, not this process's env.
            "wire_dtype": sorted(wires)[0]
            if len(wires) == 1
            else f"MIXED: {sorted(wires)}",
        },
        "seeds": seeds,
        "modes": {},
    }
    if mixed:
        summary["WARNING_mixed_step_counts"] = per_run_steps
        print(
            f"WARNING: runs of different lengths in {ART_DIR} — "
            f"{per_run_steps}; rerun the stale modes or clear the dir",
            file=sys.stderr,
        )
    for mode in modes:
        finals, to90 = [], []
        for seed in seeds:
            if (mode, seed) not in runs:
                continue
            steps, accs = curve(mode, seed)
            finals.append(accs[-1])
            hit = [s for s, a in zip(steps, accs) if a >= 0.9]
            to90.append(hit[0] if hit else None)
        summary["modes"][mode] = {
            "final_acc_mean": float(np.mean(finals)),
            "final_acc_std": float(np.std(finals)),
            "steps_to_90pct": to90,
        }
    # Trajectory deviation between each free-running mode (host-merge
    # tcp, device-resident tcpdev, overlapped tcpov) and the emulations.
    for free in ("tcp", "tcpdev", "tcpov"):
        for emu in ("ici", "stacked"):
            if free not in modes or emu not in modes:
                continue
            devs = []
            for seed in seeds:
                if (free, seed) not in runs or (emu, seed) not in runs:
                    continue
                st, at = curve(free, seed)
                se, ae = curve(emu, seed)
                common = sorted(set(st) & set(se))
                at_m = dict(zip(st, at))
                ae_m = dict(zip(se, ae))
                devs.append(max(abs(at_m[s] - ae_m[s]) for s in common))
            summary[f"max_traj_dev_{free}_vs_{emu}"] = (
                float(np.max(devs)) if devs else None
            )
    out = os.path.join(ART_DIR, "summary.json")
    with open(out, "w") as f:
        json.dump(summary, f, indent=2)
    print(json.dumps(summary, indent=2))
    return summary


# --------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    w = sub.add_parser("worker")
    w.add_argument("--peer", type=int, required=True)
    w.add_argument("--seed", type=int, required=True)
    w.add_argument("--steps", type=int, default=STEPS)
    w.add_argument("--base-port", type=int, required=True)
    w.add_argument("--out", required=True)
    w.add_argument("--grace", type=float, default=20.0)
    w.add_argument(
        "--device-resident", action="store_true",
        help="hold the replica as a JAX device array and merge on-device "
        "(exchange_on_device); TCP is only the wire",
    )
    w.add_argument(
        "--overlapped", action="store_true",
        help="overlap the partner fetch with the local step "
        "(exchange_overlapped_start/finish — SPMD overlap=True over "
        "sockets)",
    )

    r = sub.add_parser("run")
    r.add_argument("--modes", default="tcp,ici,stacked")
    r.add_argument("--seeds", default="0,1,2")
    r.add_argument("--steps", type=int, default=STEPS)
    r.add_argument(
        "--wire-dtype", choices=("f32", "bf16", "int8"), default=None,
        help="bf16 runs the whole study with the compressed wire and "
        "writes artifacts to artifacts/async_convergence_bf16w/",
    )
    r.add_argument(
        "--task", choices=("smallnet", "resnet20"), default=None,
        help="resnet20 runs the BASELINE.json:8 benchmark model on "
        "CIFAR-shaped data and writes to "
        "artifacts/async_convergence_resnet20/",
    )

    s = sub.add_parser("spmd")
    s.add_argument("--transport", choices=("ici", "stacked"), required=True)
    s.add_argument("--seed", type=int, required=True)
    s.add_argument("--steps", type=int, default=STEPS)

    sub.add_parser("analyze")

    args = ap.parse_args()
    if args.cmd == "worker":
        return tcp_worker(args)
    if args.cmd == "spmd":
        run_spmd(args.transport, args.seed, args.steps)
        return 0
    if args.cmd == "analyze":
        analyze()
        return 0

    # run: each (mode, seed) leg in its own subprocess so jax's frozen
    # platform/device-count choices never leak across legs.
    from dpwa_tpu.utils.launch import child_process_env

    global WIRE_DTYPE, ART_DIR, TASK
    if args.wire_dtype is not None:
        WIRE_DTYPE = args.wire_dtype
        os.environ["DPWA_EXP_WIRE_DTYPE"] = args.wire_dtype
    if args.task is not None:
        # Explicit flag always wins, including `--task smallnet` in a
        # shell that has DPWA_EXP_TASK exported.
        TASK = args.task
        os.environ["DPWA_EXP_TASK"] = args.task
    if args.task is not None or args.wire_dtype is not None:
        # Variant dirs compose: task and wire dtype each add a suffix, so
        # bf16 x resnet20 never clobbers the f32 resnet20 study.
        parts = ["async_convergence"]
        if TASK != "smallnet":
            parts.append(TASK)
        if WIRE_DTYPE != "f32":
            parts.append(f"{WIRE_DTYPE}w")
        ART_DIR = os.path.join(REPO_ROOT, "artifacts", "_".join(parts))
        os.environ["DPWA_EXP_ART_DIR"] = ART_DIR

    env = child_process_env(REPO_ROOT)
    for seed in [int(x) for x in args.seeds.split(",")]:
        for mode in args.modes.split(","):
            t0 = time.time()
            if mode in ("tcp", "tcpdev", "tcpov"):
                run_tcp(
                    seed, args.steps,
                    device_resident=(mode == "tcpdev"),
                    overlapped=(mode == "tcpov"),
                )
                continue
            cmd = [
                sys.executable, os.path.abspath(__file__), "spmd",
                "--transport", mode, "--seed", str(seed),
                "--steps", str(args.steps),
            ]
            subprocess.run(cmd, check=True, env=env, cwd=REPO_ROOT)
            print(f"[{mode} s{seed}] {time.time() - t0:.1f}s")
    analyze()
    return 0


if __name__ == "__main__":
    sys.exit(main())
