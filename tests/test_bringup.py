"""Bring-up contracts that the chip run depends on, checked on the CPU mesh.

Each test pins one thing `chip_smoke.py` needs to be true before a
chip-minute is spent: the device policy never swaps an accelerator for the
emulated mesh unasked, the compile cache is placed from outside, the smoke
refuses a CPU, the Llama steps trace with a Pallas kernel inside the
installed ``shard_map``, and frames land on the default device."""

import contextlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dpwa_tpu.config import make_local_config
from dpwa_tpu.utils.devices import ensure_devices
from dpwa_tpu.utils.launch import build_transport, enable_compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture(autouse=True)
def _no_cache_directory_set_in_code(monkeypatch, tmp_path):
    """`build_transport` turns the compile cache on.  With the variable set
    the helper sets no directory in code (and JAX read the variable at
    import, before it was set), so the rest of the session compiles as
    before instead of reading and writing `<checkout>/.jax_cache`."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


# ---------------------------------------------------------------------------
# Device policy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["auto", "native"])
def test_device_policy_raises_when_short_instead_of_emulating(mode):
    have = len(jax.devices())
    assert ensure_devices(have, mode=mode) == jax.devices()
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        ensure_devices(have + 1, mode=mode)
    # Nothing was repointed on the way to the error.
    assert len(jax.devices()) == have


def test_devices_cpu_still_gives_the_emulated_mesh(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "")
    devices = ensure_devices(4, mode="cpu")
    assert [d.platform for d in devices] == ["cpu"] * 4
    assert os.environ["XLA_FLAGS"].endswith("device_count=4")
    bundle = build_transport(make_local_config(4), "ici", "cpu")
    assert bundle.transport.mesh.devices.size == 4


def test_devices_cpu_refuses_a_backend_that_is_already_an_accelerator(
    monkeypatch,
):
    class FakeChip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda: [FakeChip()] * 8)
    with pytest.raises(RuntimeError, match="need 4 devices, have 8 .tpu."):
        ensure_devices(4, mode="cpu")


def test_device_policy_holds_no_backend_reset():
    import dpwa_tpu.utils.devices as devices_mod

    with open(devices_mod.__file__, encoding="utf-8") as f:
        src = f.read()
    assert "xla_bridge" not in src and "clear_backends" not in src
    assert not hasattr(devices_mod, "repoint_to_host_mesh")


def test_make_mesh_takes_a_prefix_that_is_not_a_sub_torus(monkeypatch):
    """Three peers on a four-chip host: `mesh_utils.create_device_mesh`
    refuses three chips of a 2x2 torus, so a prefix keeps enumeration
    order.  The torus comes from libtpu's compile-only topology."""
    from jax.experimental import topologies

    from dpwa_tpu.parallel.mesh import make_mesh

    try:
        chips = topologies.get_topology_desc("v5e:2x2", "tpu").devices
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"no TPU topology description: {e}")
    monkeypatch.setattr(jax, "devices", lambda: list(chips))
    ids = lambda n: [d.id for d in make_mesh(make_local_config(n)).devices]
    assert ids(3) == [0, 1, 2]
    # The whole slice is still ordered as a ring along the torus.
    assert ids(4) == [0, 1, 3, 2]


@pytest.fixture(scope="module")
def v5e_chips():
    """The chips of a described v5e 2x2: libtpu compiles for them with none
    attached.  Described here, inside a fixture of the one test file that
    loads libtpu, and never at import."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc("v5e:2x2", "tpu").devices
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"no TPU topology description: {e}")


@contextlib.contextmanager
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one: keep it out."""
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


@pytest.mark.parametrize("transport_name", ["stacked", "ici"])
def test_the_tpu_compiler_keeps_the_scopes_on_its_fusions(
    v5e_chips, transport_name
):
    """`benchmark/scopes.py` splits a step's device time by the `op_name` of
    each executed instruction, so the `dpwa.*` scopes have to survive
    XLA:TPU's fusion, on both step builders."""
    import re

    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.parallel.stacked import (
        StackedTrainState, StackedTransport, make_stacked_train_step,
    )
    from dpwa_tpu.train import GossipTrainState, make_gossip_train_step

    n, cfg = 4, make_local_config(4, schedule="ring")
    if transport_name == "stacked":
        transport, State = StackedTransport(cfg), StackedTrainState
        make_step = make_stacked_train_step
        peer = replicated = SingleDeviceSharding(v5e_chips[0])
    else:
        mesh = Mesh(np.array(v5e_chips[:n]), ("peers",))
        transport, State = IciTransport(cfg, mesh=mesh), GossipTrainState
        make_step = make_gossip_train_step
        peer, replicated = NamedSharding(mesh, P("peers")), NamedSharding(mesh, P())

    def loss_fn(params, batch):
        x, y = batch
        hidden = jnp.tanh(x @ params["w1"])
        return jnp.mean((hidden @ params["w2"] - y) ** 2)

    optimizer = optax.sgd(0.1, momentum=0.9)
    shaped = lambda shape, sh=peer: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=sh
    )
    params = {"w1": shaped((n, 128, 256)), "w2": shaped((n, 256, 128))}
    state = State(
        params=params,
        opt_state=jax.tree.map(
            lambda s: shaped(s.shape),
            jax.eval_shape(jax.vmap(optimizer.init), params),
        ),
        clock=shaped((n,)),
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated),
        model_state=None, loss=shaped((n,)),
    )
    batch = (shaped((n, 64, 128)), shaped((n, 64, 128)))
    with _no_compile_cache():
        text = jax.jit(make_step(loss_fn, optimizer, transport)).lower(
            state, batch
        ).compile().as_text()
    named = lambda hlo: [
        (re.search(r'op_name="([^"]*)"', line) or [None, ""])[1]
        for line in hlo.splitlines() if " fusion(" in line
    ]
    # The entry computation's fusions run as instructions of their own (a
    # fusion inside a fused computation does not, and has no name).
    entry = named(text[text.index("\nENTRY "):])
    assert len(entry) >= 4, text[-3000:]
    assert sum(bool(name) for name in entry) >= 0.8 * len(entry), entry
    # Forward and backward are each on some fusion, and on one chip the
    # exchange's gather is too.  The optimizer's arithmetic, and across chips
    # this toy's merge, may be fused into another's and keep their names
    # inside; there the exchange is the collective, checked below.
    for part in ("jvp(dpwa.forward)", "transpose(jvp(dpwa.forward))"):
        assert any(part in name for name in entry), (part, entry)
    if transport_name == "stacked":
        assert any("dpwa.exchange" in name for name in entry), entry
    assert "dpwa.optimizer" in text and "dpwa.exchange" in text
    if transport_name == "ici":
        moved = re.findall(
            r'collective-permute(?:-start)?\([^\n]*op_name="([^"]*)"', text
        )
        assert moved and all("dpwa.exchange" in name for name in moved)


@pytest.mark.parametrize(
    "B,T,head_dim",
    [
        (1, 256, 128),  # run.py's model check in both decoder cells
        (1, 384, 128),  # one block of 384
        (1, 640, 128),  # majors of 640 over inner blocks of 128
        (1, 1536, 128),  # 768 over 384
        (8, 512, 128),  # mistral7b-lora-stacked2-t512
        (2, 1024, 128),  # chip_smoke.py
        (1, 4096, 128),  # mistral7b-lora-stacked2-t4096
        (1, 8192, 128),
        (1, 512, 256),
        (1, 4096, 256),
        (1, 4096, 512),  # dk, dv of a whole head over the ceiling: the library,
                         # whose tiles grow with the head size: the caps shrink
        (1, 16384, 128),  # likewise
        (2, 512, 64),  # a head of 64 stays on the library
        (1, 4096, 64),  # lfm2-lora-stacked2-t4096
    ],
)
def test_the_chosen_flash_blocks_fit_the_v5e(v5e_chips, B, T, head_dim):
    """Mosaic compiles the forward and backward kernels of the family
    `single_device_attention` picks for the shape, with the window and the
    blocks it chooses (a candidate that does not fit the VMEM it may ask for
    is refused here, with no chip), under `vmap` over peers as the stacked
    step runs them.  Our causal kernels carry the two prefixes the trace's
    readers know a flash kernel by; the library's backward kernels carry the
    chosen sizes in their names, which is how a trace shows them."""
    import dataclasses
    import functools

    from jax.sharding import SingleDeviceSharding

    from dpwa_tpu.ops import eva
    from dpwa_tpu.ops.ulysses import (
        _flash_block_sizes, single_device_attention,
    )

    attn = jax.vmap(
        functools.partial(single_device_attention, causal=True, impl="flash")
    )
    loss = lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32))
    one = SingleDeviceSharding(v5e_chips[0])
    shaped = lambda heads: jax.ShapeDtypeStruct(
        (2, B, T, heads, head_dim), jnp.bfloat16, sharding=one
    )
    with _no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shaped(8), shaped(2), shaped(2)
        ).compile().as_text()
    ours = eva.causal_kernels_take(T, head_dim, 8, 2, jnp.bfloat16)
    assert ours == (head_dim % 128 == 0 and T * head_dim < 16384 * 128)
    if ours:
        assert text.count("tpu_custom_call") == 2
        for name in eva.KERNEL_NAMES[False]:
            assert name in text, name
        assert "flash_mha_bwd_dq" not in text
        # Keys and values stay grouped: no array of eight heads of them.
        assert f"bf16[{2 * B},2,{T},{head_dim}]" in text
        return
    assert text.count("tpu_custom_call") >= 3
    b = dataclasses.asdict(_flash_block_sizes(T, head_dim))
    for name in (
        "flash_mha_bwd_dkv_block_q_major_{block_q_major_dkv}"
        "_block_q_{block_q_dkv}_block_k_major_{block_k_major_dkv}"
        "_block_k_{block_k_dkv}",
        "flash_mha_bwd_dq_block_q_major_{block_q_dq}"
        "_block_k_major_{block_k_major_dq}_block_k_{block_k_dq}",
    ):
        assert name.format(**b) in text, name.format(**b)


@pytest.mark.parametrize("T", [256, 512, 1024])
def test_latent_attention_heads_go_to_the_kernels_padded(v5e_chips, T):
    """Latent attention's q and k heads of 192 and v heads of 128 take the
    kernel path (`impl="auto"`, answering as the chip), padded to 256:
    Mosaic compiles forward, dkv and dq for 64 heads under `vmap` over two
    peers, and the result keeps v's head size."""
    import functools
    import re
    from unittest import mock

    from jax.sharding import SingleDeviceSharding

    from dpwa_tpu.ops.ulysses import single_device_attention

    attn = jax.vmap(functools.partial(
        single_device_attention, causal=True, sm_scale=0.1309
    ))
    loss = lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32))
    one = SingleDeviceSharding(v5e_chips[0])
    shaped = lambda d: jax.ShapeDtypeStruct(
        (2, 1, T, 64, d), jnp.bfloat16, sharding=one
    )
    with _no_compile_cache(), mock.patch.object(
        jax, "default_backend", lambda: "tpu"
    ):
        out = jax.eval_shape(attn, shaped(192), shaped(192), shaped(128))
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shaped(192), shaped(192), shaped(128)
        ).compile().as_text()
    assert out.shape == (2, 1, T, 64, 128)
    assert text.count("tpu_custom_call") >= 3
    # What the kernels are handed: heads first, every head size 256.
    assert re.search(r"bf16\[(\d+,)*64,%d,256\]" % T, text)


def test_a_share_of_the_experts_compiles_a_call_a_peer(v5e_chips):
    """`ops/moe.held_matmul` at the published widths under `vmap` over two
    peers: 8 held experts a peer, 4,096 rows a peer of which the groups
    cover a few, forward and both gradients.  Mosaic compiles the `megablox`
    kernels with a skipped leading group (`group_offset`) writing into one
    folded result (`existing_out`): a call a peer, no slice of the rows."""
    from unittest import mock

    from jax.sharding import SingleDeviceSharding

    from dpwa_tpu.ops import moe

    one = SingleDeviceSharding(v5e_chips[0])
    shaped = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one
    )

    def loss(rows, kernel, sizes):
        out = jax.vmap(moe.held_matmul)(rows, kernel, sizes)
        return jnp.sum(out.astype(jnp.float32))

    with _no_compile_cache(), mock.patch.object(
        jax, "default_backend", lambda: "tpu"
    ):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            shaped((2, 4096, 7168)), shaped((2, 8, 7168, 2048)),
            shaped((2, 8), jnp.int32),
        ).compile().as_text()
    # gmm forward is dead code under grad of a sum; to the rows and to the
    # weights: two kernels a peer.
    assert text.count("tpu_custom_call") >= 4
    assert "bf16[8192,7168]" in text  # the folded rows


@pytest.mark.parametrize("T", [1024, 4096])  # the model check's, the cell's
def test_the_selective_scan_compiles_with_its_state_off_the_hbm(v5e_chips, T):
    """`ops/ssm.selective_scan` at the published widths (5,120 channels, 16
    states, bfloat16 stream) under `vmap` over two peers, forward and all six
    gradients: Mosaic compiles both kernels, the peers are folded into their
    sequence axis, and no array of the compiled program comes near the `T x
    5120 x 16` values of the state over time."""
    import re
    from unittest import mock

    from jax.sharding import SingleDeviceSharding

    from dpwa_tpu.ops import ssm

    one = SingleDeviceSharding(v5e_chips[0])
    shaped = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one
    )

    def loss(*args):
        out = jax.vmap(ssm.selective_scan)(*args)
        return jnp.sum(out.astype(jnp.float32))

    with _no_compile_cache(), mock.patch.object(
        jax, "default_backend", lambda: "tpu"
    ):
        text = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)))).lower(
            shaped((2, 1, T, 5120), jnp.bfloat16), shaped((2, 1, T, 5120)),
            shaped((2, 5120, 16)), shaped((2, 1, T, 16), jnp.bfloat16),
            shaped((2, 1, T, 16), jnp.bfloat16), shaped((2, 5120)),
        ).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "dpwa_selective_scan_fwd" in text and "dpwa_selective_scan_bwd" in text
    assert f"bf16[2,{T},5120]" in text  # the folded sequences
    sizes = [
        int(np.prod([int(d) for d in dims.split(",")]))
        for dims in re.findall(r"(?:f32|bf16|s32|u32|pred)\[([\d,]+)\]", text)
    ]
    # The largest is a peer-stacked [2, 1, T, 5120]; the state over time of
    # one sequence alone would be 8 times that.
    assert max(sizes) == 2 * T * 5120 < T * 5120 * 16


@pytest.mark.parametrize("peers, T", [(2, 16384), (1, 6144)])  # the cell's, the model check's
def test_the_eva_core_compiles_with_its_scores_off_the_hbm(v5e_chips, peers, T):
    """`ops/eva.eva_attention` at the published sizes (32 heads of 128, a
    window of 2,048 in chunks of 16, bfloat16) under `vmap` over the peers,
    forward and all five gradients: Mosaic compiles both kernels, the peers
    are folded into their sequence axis, and no array of the compiled
    program comes within eight times of the `T x (window + T / 16)` scores
    a head."""
    import re
    from unittest import mock

    from jax.sharding import SingleDeviceSharding

    from dpwa_tpu.ops import eva

    one = SingleDeviceSharding(v5e_chips[0])
    shaped = lambda steps: jax.ShapeDtypeStruct(
        (peers, 1, 32, steps, 128), jnp.bfloat16, sharding=one
    )

    def loss(*args):
        out = jax.vmap(
            lambda *a: eva.eva_attention(*a, window=2048, chunk=16)
        )(*args)
        return jnp.sum(out.astype(jnp.float32))

    with _no_compile_cache(), mock.patch.object(
        jax, "default_backend", lambda: "tpu"
    ):
        text = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(5)))).lower(
            *3 * [shaped(T)], *2 * [shaped(T // 16)]
        ).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "dpwa_eva_attention_fwd" in text and "dpwa_eva_attention_bwd" in text
    assert f"bf16[{peers},32,{T},128]" in text  # the folded sequences
    sizes = [
        int(np.prod([int(d) for d in dims.split(",")]))
        for dims in re.findall(r"(?:f32|bf16|s32|u32|pred)\[([\d,]+)\]", text)
    ]
    # The largest is a peer-stacked q, 128 values a query and head; the
    # scores would be 3,072 (at T 6,144: 2,432).
    assert max(sizes) == peers * 32 * T * 128
    assert 128 < (2048 + T // 16) // 8


def test_the_eva_cells_step_lowers_with_both_kernels_and_no_scores():
    """The cell's own loss under `vmap` over its two peers at the published
    sizes (4 layers, T 16,384), differentiated and lowered for a TPU (shapes
    alone: no chip, no TPU compiler): the forward kernel once a layer and
    once more in each block's recomputation, the backward kernel once a
    layer, and no tensor of one sequence's `T x (window + T / 16)` scores
    over its 32 heads: the largest is the SwiGLU's `[2, 1, 16384, 11008]`."""
    import re
    from unittest import mock

    from tests.yardstick.yardstick_paths import cell_files

    from benchmark.builders import eva_decoder

    _, config, cell = cell_files("evabyte-lora-stacked2-t16384")
    built = eva_decoder.build(config, cell)
    T = cell["seq_len"]
    shapes = jax.eval_shape(
        jax.vmap(built.init_fn), jax.random.split(jax.random.key(0), 2)
    )
    tokens = jax.ShapeDtypeStruct((2, 1, T), jnp.int32)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(jax.vmap(jax.grad(built.loss_fn))).trace(
            shapes, (tokens, tokens)
        ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "tpu_custom_call" in text
    calls = re.findall(
        r'loc\("([^"]*)/layer_(\d)/[^"]*dpwa_eva_attention_(fwd|bwd)/', text
    )
    where = lambda kernel, inside: sorted(
        int(layer) for scope, layer, k in calls
        if k == kernel and ("rematted_computation" in scope) == inside
    )
    assert where("fwd", False) == where("fwd", True) == [0, 1, 2, 3]
    assert where("bwd", False) == [0, 1, 2, 3] and where("bwd", True) == []
    sizes = [
        int(np.prod([int(d) for d in dims.split("x")]))
        for dims in re.findall(r"tensor<((?:\d+x)+)(?:f32|bf16|i32|i1)>", text)
        for dims in [dims.rstrip("x")]
    ]
    assert max(sizes) == 2 * T * 11008 < 32 * T * (2048 + T // 16)


def test_the_lfm2_cells_step_lowers_with_its_kernels_and_no_scores():
    """The LFM2 cell's own loss under `vmap` over its two peers at the
    published sizes (5 layers, T 4,096), differentiated and lowered for a TPU
    (shapes alone: no chip, no TPU compiler): the expert layers' `gmm` and
    `tgmm` and the library's three flash kernels are custom calls (the head
    size of 64 goes to the kernels, as it is, and not to the einsum), the gate
    of the four conv mixers is there under its name, and no tensor holds one
    sequence's `T x T` scores over its 32 heads: the largest is the float32
    logits `[2, 1, 4096, 65536]`, half of what the scores would be."""
    import re
    from unittest import mock

    from tests.yardstick.yardstick_paths import cell_files

    from benchmark.builders import conv_moe_decoder

    _, config, cell = cell_files("lfm2-lora-stacked2-t4096")
    assert cell["expect_hlo"] == ["tpu_custom_call"]
    built = conv_moe_decoder.build(config, cell)
    T = cell["seq_len"]
    shapes = jax.eval_shape(
        jax.vmap(built.init_fn), jax.random.split(jax.random.key(0), 2)
    )
    tokens = jax.ShapeDtypeStruct((2, 1, T), jnp.int32)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(jax.vmap(jax.grad(built.loss_fn))).trace(
            shapes, (tokens, tokens)
        ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "tpu_custom_call" in text
    kernels = set(re.findall(r'kernel_name = "([^"]+)"', text))
    assert {
        "_flash_attention_kernel", "_flash_attention_dkv_kernel",
        "_flash_attention_dq_kernel",
    } <= kernels
    # The grouped products in the four expert layers (2 to 4 once more in a
    # block's recomputation), `tgmm` in the backward pass alone.
    calls = re.findall(
        r'loc\("([^"]*)/layer_(\d)/mlp/[^"]*dpwa\.moe\.experts/jit\((t?gmm)\)',
        text,
    )
    layers = lambda kernel: sorted({int(i) for _, i, k in calls if k == kernel})
    assert layers("gmm") == layers("tgmm") == [1, 2, 3, 4]
    assert all("transpose(jvp" in scope for scope, _, k in calls if k == "tgmm")
    # The flash kernels see the heads of 64 as they are: nothing is padded.
    assert "tensor<2x1x32x4096x64xbf16>" in text
    assert "x128xbf16>" not in text
    gate = re.findall(r'layer_(\d)/conv/dpwa\.conv/dpwa\.conv\.gate/', text)
    assert sorted(set(map(int, gate))) == [0, 2, 3, 4]
    sizes = [
        int(np.prod([int(d) for d in dims.split("x")]))
        for dims in re.findall(r"tensor<((?:\d+x)+)(?:f32|bf16|i32|i1)>", text)
        for dims in [dims.rstrip("x")]
    ]
    assert max(sizes) == 2 * T * 65536 < 2 * 32 * T * T


def test_the_mellum_cells_step_lowers_with_its_kernels_and_no_scores():
    """The Mellum cell's own loss under `vmap` over its two peers at the
    published sizes (4 layers, T 4,096, a window of 1,024), differentiated and
    lowered for a TPU (shapes alone: no chip, no TPU compiler): the expert
    layers' `gmm` and `tgmm` are custom calls, the three sliding layers run
    the windowed forward and backward kernels and the full layer the causal
    ones (each layer's forward once more in its block's recomputation), and
    no tensor holds a head's `T x T` scores: the masked softmax was not
    taken."""
    import re
    from unittest import mock

    from tests.yardstick.yardstick_paths import cell_files

    from benchmark.builders import window_moe_decoder
    from dpwa_tpu.ops import eva

    _, config, cell = cell_files("mellum2-lora-stacked2-t4096")
    assert cell["expect_hlo"] == ["tpu_custom_call"]
    built = window_moe_decoder.build(config, cell)
    T = cell["seq_len"]
    shapes = jax.eval_shape(
        jax.vmap(built.init_fn), jax.random.split(jax.random.key(0), 2)
    )
    tokens = jax.ShapeDtypeStruct((2, 1, T), jnp.int32)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = jax.jit(jax.vmap(jax.grad(built.loss_fn))).trace(
            shapes, (tokens, tokens)
        ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "tpu_custom_call" in text
    calls = re.findall(
        r'loc\("([^"]*)/layer_(\d)/mlp/[^"]*dpwa\.moe\.experts/jit\((t?gmm)\)',
        text,
    )
    layers = lambda kernel: sorted({int(i) for _, i, k in calls if k == kernel})
    assert layers("gmm") == layers("tgmm") == [0, 1, 2, 3]
    assert all("transpose(jvp" in scope for scope, _, k in calls if k == "tgmm")
    # Which attention kernel each layer calls: a kernel's call is one shared
    # body a kernel (``jax.jit`` around it), named by its ``kernel_name``,
    # and a layer's call of that body carries the layer's scopes.
    forward, backward = eva.KERNEL_NAMES[False]
    alias = dict(re.findall(r'(#loc\d+) = loc\("([^"]*)"', text))
    kernel_of = dict(re.findall(
        r'func\.func private @"(<unknown>[^"]*)"(?:(?!func\.func).)*?'
        r'kernel_name = "([^"]+)"', text, re.S,
    ))
    assert sorted(kernel_of.values()) == sorted(
        (forward, backward) + eva.BAND_KERNEL_NAMES
    )
    called = [
        (kernel_of[fn], alias[loc]) for fn, loc in re.findall(
            r'call @"(<unknown>[^"]*)"\(.*?loc\((#loc\d+)\)\n', text
        )
    ]
    assert len(called) == 4 * 3  # forward, recomputed and backward, a layer
    by_layer = lambda i: {
        name for name, where in called if f"/layer_{i}/dpwa.attn.gqa/" in where
    }
    for i in range(3):
        assert by_layer(i) == set(eva.BAND_KERNEL_NAMES), i
    assert by_layer(3) == {forward, backward}
    for name, where in called:
        assert ("dpwa.attn.gqa/dpwa.attn.window/attn/" in where) == (
            name in eva.BAND_KERNEL_NAMES
        )
        assert ("transpose(jvp" in where) == (
            name.startswith("flash_mha_bwd") or "rematted" in where
        )
    # Keys and values go to the kernels grouped, four heads of them.
    assert "tensor<2x1x4x4096x128xbf16>" in text
    sizes = [
        int(np.prod([int(d) for d in dims.split("x")]))
        for dims in re.findall(r"tensor<((?:\d+x)+)(?:f32|bf16|i32|i1)>", text)
        for dims in [dims.rstrip("x")]
    ]
    # The largest are one projection of the two peers' 64 experts and the
    # float32 logits: under half of one sequence's scores over its 32 heads.
    assert max(sizes) == 2 * 64 * 2304 * 896 < 32 * T * T // 2
    assert 2 * T * 24576 in sizes


@pytest.mark.parametrize("T, window", [(4096, 1024), (8192, 1024), (1024, 256)])
def test_the_windowed_kernels_fit_the_v5e(v5e_chips, T, window):
    """Mosaic compiles the forward and backward kernels with a band at the
    Mellum cell's heads (32 on 4 of 128) under `vmap` over two peers, at the
    cell's T, at twice it and where the band is cut inside one block."""
    import functools

    from jax.sharding import SingleDeviceSharding

    from dpwa_tpu.ops import eva
    from dpwa_tpu.ops.ulysses import single_device_attention

    attn = jax.vmap(functools.partial(
        single_device_attention, causal=True, window=window, impl="flash"
    ))
    loss = lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32))
    one = SingleDeviceSharding(v5e_chips[0])
    shaped = lambda heads: jax.ShapeDtypeStruct(
        (2, 1, T, heads, 128), jnp.bfloat16, sharding=one
    )
    with _no_compile_cache():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shaped(32), shaped(4), shaped(4)
        ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    for name in eva.BAND_KERNEL_NAMES:
        assert name in text, name
    assert f"bf16[2,4,{T},128]" in text


MAMBA_LAYERS = 6


def _jamba_step_of_mamba_blocks():
    """``(bytes, compiled text)`` of the Jamba cell's own step at
    ``MAMBA_LAYERS`` layers (Mamba blocks all: the attention layer is the
    eighth) compiled for the described v5e, by
    ``benchmark/rehearse_compile.py``: the bytes from its report, the text
    caught on its way into it."""
    import contextlib
    import io
    from unittest import mock

    from benchmark import rehearse_compile

    texts, as_text = [], jax.stages.Compiled.as_text

    def caught(compiled, *args, **kwargs):
        texts.append(as_text(compiled, *args, **kwargs))
        return texts[-1]

    printed = io.StringIO()
    with _no_compile_cache(), mock.patch.object(
        jax.stages.Compiled, "as_text", caught
    ), contextlib.redirect_stdout(printed):
        assert rehearse_compile.main([
            "jamba2-lora-period14-stacked2", "--no-reference",
            "--set", f"num_hidden_layers={MAMBA_LAYERS}",
        ]) == 0
    report = json.loads(printed.getvalue().splitlines()[-1])
    assert report["step"]["tpu_custom_call"]
    return report["step"]["total_gb"] * 1e9, texts[0]


@pytest.fixture(scope="module")
def jamba_step(v5e_chips):
    """The step as the tree has it, compiled once for the tests below."""
    return _jamba_step_of_mamba_blocks()


def test_what_the_mamba_blocks_keep_does_not_pile_up(jamba_step):
    """The Jamba cell's own step at six layers (Mamba blocks all, each
    keeping its scan's output and boundary states) compiled for the v5e
    holds no more than the same step with no block keeping anything plus
    what the six keep: bfloat16 ``[2, 4096, 5120]`` and float32 ``[2, 32,
    16, 5120]`` a block, 105 MB.  Not today's number: the guard against a
    change that makes more than the kept arrays stand at the step's peak.
    One such is planted: with the name on ``y`` itself ``jax.checkpoint``
    (jax 0.9.0, ``ad_checkpoint._insert_reduce_precision``) rounds the
    saved float and a ``y`` stands at the peak twice; it is what
    ``ops/ssm._named_bits`` is for, and the day this half fails that
    function has nothing left to do.  (Four layers and "breaks the bound"
    until PR 56: with the convolution's float32 arrays gone the peak of so
    shallow a step lies where no second ``y`` reaches it, 5.632 GB either
    way; at six layers one does, 7.331 against 7.243 GB, and at the cell's
    fourteen 12.466 against 12.232 by the compiler's assignment.)"""
    from unittest import mock

    from dpwa_tpu.models import llama
    from dpwa_tpu.ops import ssm

    kept, _ = jamba_step
    with mock.patch.object(ssm, "_named_bits", ssm.checkpoint_name):
        kept_as_floats, _ = _jamba_step_of_mamba_blocks()
    with mock.patch.object(llama, "_checkpoint_policy", lambda cfg, i: None):
        nothing_kept, _ = _jamba_step_of_mamba_blocks()
    a_y = 2 * 4096 * 5120 * 2
    a_block = a_y + 2 * 32 * 16 * 5120 * 4
    assert kept <= nothing_kept + MAMBA_LAYERS * a_block, (kept, nothing_kept)
    assert kept_as_floats >= kept + a_y, (kept_as_floats, kept)


def test_the_mamba_blocks_run_the_convolution_as_its_two_kernels(jamba_step):
    """The same step's compiled text: the convolution with silu is
    ``ops/ssm.conv_silu``'s two kernels, and nothing booked under
    ``dpwa.ssm.conv`` writes a float32 array of ``x``'s size (the widened
    copy and the four widened gradients XLA built around ``causal_conv1d``
    until PR 56, 168 MB each)."""
    import re

    from dpwa_tpu.utils import scopes

    _, text = jamba_step
    # A layer: forward, recomputed, backward.
    calls = lambda name: len(re.findall(name + r"[.\d]* = ", text))
    assert calls("dpwa_conv_silu_fwd") == 2 * MAMBA_LAYERS
    assert calls("dpwa_conv_silu_bwd") == MAMBA_LAYERS
    entry = text[text.index("\nENTRY "):]
    result = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) [\w\-]+\(")
    wide = re.compile(r"f32\[2,(?:1,)?4096,5120\]")
    written = [
        line[:200] for line in entry.splitlines()
        if scopes.SSM_PARTS.conv in line and (m := result.match(line))
        and wide.search(m.group(1))
    ]
    assert not written, written


# ---------------------------------------------------------------------------
# Compile cache
# ---------------------------------------------------------------------------


def test_compile_cache_obeys_the_environment(tmp_path):
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        first = enable_compile_cache()
        assert first == os.path.join(_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert enable_compile_cache() == first  # no pid, clock or tempdir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------


def test_chip_smoke_refuses_a_cpu_without_the_rehearsal_argument(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) == chip_smoke.EXIT_NO_ACCELERATOR
    out = capsys.readouterr()
    assert out.out == "" and "not a TPU" in out.err


def test_chip_smoke_rehearsal_leg_passes(tmp_path, capsys):
    rc = chip_smoke.main(["--rehearse-cpu", "--legs", "host_device_merge"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert rc == 0, lines
    leg, cache, last = lines
    assert leg["leg"] == "host_device_merge" and leg["status"] == "ok"
    assert leg["handoff"]["h2d_transfers"] == 3
    assert cache["leg"] == "compile_cache" and cache["dir"] == str(tmp_path)
    assert last["ok"] is True and last["device"]["platform"] == "cpu"
    assert set(last["device"]) == {"platform", "kind", "count"}


@pytest.mark.slow
def test_chip_smoke_full_rehearsal_passes(capsys):
    rc = chip_smoke.main(["--rehearse-cpu"])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert rc == 0, lines
    assert [r["status"] for r in lines[:-2]] == ["ok"] * 6


@pytest.mark.parametrize("transport", ["ici", "stacked"])
def test_train_step_compiles_once(transport):
    """The state a step hands back has the signature of the state it was
    given (the scalar ``step`` used to come back committed and replicated,
    and every ICI run compiled its train step twice — 49 s for ResNet-50 on
    the v5e).  ``run_steps`` fails when a later call lowers a program."""
    n = 2
    bundle = build_transport(make_local_config(n), transport)
    params = {"w": jnp.ones((n, 8, 4)), "b": jnp.zeros((n, 4))}
    opt = optax.sgd(0.1, momentum=0.9)
    state = bundle.init_state(params, opt, bundle.transport)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    step_fn = bundle.make_step(loss_fn, opt, bundle.transport)
    batch = jax.device_put(
        (np.ones((n, 2, 8), np.float32), np.ones((n, 2, 4), np.float32)),
        bundle.batch_sharding,
    )
    _, losses, _, _, later = chip_smoke.run_steps(step_fn, state, batch, 2)
    assert len(later) == 2 and losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# A Pallas kernel inside the installed shard_map (the chip's Llama path)
# ---------------------------------------------------------------------------

_LLAMA = dict(
    vocab_size=64, d_model=256, n_heads=2, n_kv_heads=1, d_ff=64,
    n_layers=1, max_seq_len=512, lora_rank=2,
)


def _abstract_llama_state(n, sharding):
    from dpwa_tpu.models.llama import Llama, LlamaConfig, lora_optimizer
    from dpwa_tpu.train import GossipTrainState, init_params_per_peer

    init_model = Llama(LlamaConfig(**_LLAMA))
    params = jax.eval_shape(
        lambda: init_params_per_peer(
            lambda k: init_model.init(k, jnp.zeros((1, 8), jnp.int32)),
            jax.random.key(0), n,
        )
    )
    opt = lora_optimizer(
        optax.sgd(0.1),
        jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(v.shape[1:], v.dtype), params
        ),
    )
    spec = lambda t: jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding), t
    )
    per_peer = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=sharding)
    state = GossipTrainState(
        params=spec(params),
        opt_state=spec(jax.eval_shape(jax.vmap(opt.init), params)),
        clock=per_peer,
        step=jax.ShapeDtypeStruct((), jnp.int32),
        loss=per_peer,
    )
    return opt, state


def _kernels_traced(step_fn, state, batch):
    """``(pallas calls in the step, whether two of them are the forward and
    the backward kernel of causal attention at a head of 128)``:
    `ops/eva.causal_attention`'s; the library's family, which ran here before
    PR 46, has three."""
    from dpwa_tpu.ops import eva

    text = str(jax.make_jaxpr(step_fn)(state, batch))
    ours = all(f"name={name}" in text for name in eva.KERNEL_NAMES[False])
    return text.count("pallas_call"), ours


def test_llama_1d_step_traces_with_the_flash_kernel_inside_shard_map():
    from dpwa_tpu.models.llama import Llama, LlamaConfig, lora_filter
    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.parallel.mesh import peer_sharding
    from dpwa_tpu.train import make_gossip_train_step

    n, T = 2, 128
    transport = IciTransport(make_local_config(n))
    sh = peer_sharding(transport.mesh)
    opt, state = _abstract_llama_state(n, sh)
    model = Llama(LlamaConfig(**_LLAMA, attn_impl="flash"))

    def loss_fn(params, batch):
        x, y = batch
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(params, x), y
        ).mean()

    step_fn = make_gossip_train_step(
        loss_fn, opt, transport, exchange_filter=lora_filter
    )
    tokens = jax.ShapeDtypeStruct((n, 1, T), jnp.int32, sharding=sh)
    # Forward and backward kernels: refused outright by a checked map.
    calls, ours = _kernels_traced(step_fn, state, (tokens, tokens))
    assert calls >= 2 and ours


@pytest.mark.parametrize(
    "variant",
    [
        dict(),
        dict(sp_layout="zigzag"),
        dict(sp_strategy="a2a", attn_impl="flash"),
    ],
    ids=["ring", "zigzag", "a2a"],
)
def test_llama_2d_step_traces_with_pallas_hops_inside_shard_map(
    monkeypatch, variant
):
    from dpwa_tpu.models.llama import Llama, LlamaConfig, lora_filter
    from dpwa_tpu.parallel.ici import IciTransport
    from dpwa_tpu.parallel.mesh import peer_sharding
    from dpwa_tpu.train_sp import (
        make_gossip_sp_train_step,
        make_sp_mesh,
        sp_batch_sharding,
    )

    # The dispatchers answer as on the chip, so the ring hops are the
    # Pallas kernels; tracing needs no TPU, only lowering would.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, sp, T_local = 2, 2, 256
    cfg = make_local_config(n)
    mesh = make_sp_mesh(cfg, sp)
    transport = IciTransport(cfg, mesh=mesh)
    opt, state = _abstract_llama_state(n, peer_sharding(mesh))
    model = Llama(LlamaConfig(**_LLAMA, sp_axis="sp", **variant))

    def sp_loss(params, batch):
        x, y = batch
        losses = optax.softmax_cross_entropy_with_integer_labels(
            model.apply(params, x), y
        )
        return losses.sum(), jnp.float32(losses.size)

    step_fn = make_gossip_sp_train_step(
        sp_loss, opt, transport, exchange_filter=lora_filter
    )
    tokens = jax.ShapeDtypeStruct(
        (n, 1, sp * T_local), jnp.int32, sharding=sp_batch_sharding(mesh)
    )
    # The ring's hops are a forward, a dq and a dkv kernel; the a2a strategy
    # runs `single_device_attention`'s forward and backward kernel.
    a2a = variant.get("sp_strategy") == "a2a"
    calls, ours = _kernels_traced(step_fn, state, (tokens, tokens))
    assert calls >= (2 if a2a else 3) and ours == a2a


# ---------------------------------------------------------------------------
# Host frames onto the device
# ---------------------------------------------------------------------------


def _aligned(n: int) -> np.ndarray:
    from dpwa_tpu.device.handoff import ALIGN

    raw = np.zeros(n * 4 + ALIGN, np.uint8)
    off = (-raw.ctypes.data) % ALIGN
    out = raw[off:off + n * 4].view(np.float32)
    out[:] = np.arange(n)
    return out


def test_to_device_lands_on_the_default_device(monkeypatch):
    from dpwa_tpu.device import handoff

    frame = _aligned(1024)
    handoff.reset_handoff_stats()
    adopted = handoff.to_device(frame)
    assert adopted.devices() == {jax.devices()[0]}
    assert handoff.handoff_stats()["h2d_zero_copy"] == 1
    # On an accelerator backend a dlpack import would stay on the CPU
    # backend, away from the replica: the frame is put on the device.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    put = handoff.to_device(frame)
    assert put.devices() == {jax.devices()[0]}
    stats = handoff.handoff_stats()
    assert (stats["h2d_transfers"], stats["h2d_zero_copy"]) == (2, 1)
    np.testing.assert_array_equal(np.asarray(put), frame)
