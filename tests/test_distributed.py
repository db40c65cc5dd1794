import os
import socket
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dpwa_tpu.config import make_local_config
from dpwa_tpu.interpolation import PeerMeta
from dpwa_tpu.parallel.distributed import (
    DcnHierarchicalTransport,
    hierarchical_config_for_hosts,
)
from dpwa_tpu.parallel.mesh import make_mesh


def test_hierarchical_config_for_hosts():
    cfg = make_local_config(8, schedule="ring")
    out = hierarchical_config_for_hosts(cfg, chips_per_host=4)
    assert out.protocol.schedule == "hierarchical"
    assert out.protocol.group_size == 4
    with pytest.raises(ValueError):
        hierarchical_config_for_hosts(make_local_config(6), chips_per_host=4)


def test_dcn_transport_auto_hierarchical():
    cfg = make_local_config(8, schedule="ring")  # not hierarchical yet
    t = DcnHierarchicalTransport(
        hierarchical_config_for_hosts(cfg, chips_per_host=4),
        mesh=make_mesh(make_local_config(8)),
    )
    assert t.schedule.name == "hierarchical"
    groups = np.arange(8) // 4
    # Last pool slot crosses hosts, earlier slots stay inside.
    perm = t.schedule.pool[-1]
    assert (groups[perm] != groups).all()
    for slot in range(t.schedule.pool_size - 1):
        perm = t.schedule.pool[slot]
        assert (groups[perm] == groups).all()


def test_dcn_transport_exchanges():
    cfg = hierarchical_config_for_hosts(
        make_local_config(8), chips_per_host=4
    )
    t = DcnHierarchicalTransport(cfg, mesh=make_mesh(cfg))
    params = {"w": jnp.arange(8.0)[:, None] * jnp.ones((8, 4))}
    meta = PeerMeta(jnp.ones(8), jnp.ones(8))
    for step in range(t.schedule.pool_size):
        params, info = t.exchange(params, meta, step)
        partner = np.asarray(info.partner)
        np.testing.assert_array_equal(partner[partner], np.arange(8))
    # After a full period every peer has mixed with its group and across.
    w = np.asarray(params["w"])[:, 0]
    assert w.std() < np.arange(8.0).std()


def test_multiprocess_dcn_smoke():
    """2 OS processes x 4 emulated CPU devices: real jax.distributed
    bring-up (gloo collectives across the process boundary) driving the
    DcnHierarchicalTransport exchange — the first true multi-process
    execution of parallel/distributed.py (SURVEY.md §2 DCN backend row)."""
    worker = os.path.join(os.path.dirname(__file__), "dcn_worker.py")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    from dpwa_tpu.utils.launch import child_process_env

    repo_root = os.path.dirname(os.path.dirname(worker))
    # platform=None: the worker pins its own platform after distributed
    # init; pre-setting JAX_PLATFORMS here would be redundant.
    env = child_process_env(repo_root, platform=None)
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), str(port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            cwd=repo_root,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:  # pragma: no cover
        for p in procs:
            p.kill()
        pytest.fail(f"dcn workers hung; partial output: {outs}")
    for p, out in zip(procs, outs):
        if "DCN_SKIP" in out:  # pragma: no cover - environment-dependent
            pytest.skip(f"jax.distributed unavailable: {out.splitlines()[-1]}")
        assert p.returncode == 0, out
        assert "DCN_OK" in out, out
