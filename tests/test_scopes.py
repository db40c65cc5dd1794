"""The step names its own parts: ``dpwa.forward`` / ``dpwa.optimizer`` /
``dpwa.exchange`` in the ``op_name`` of the lowered step of all three step
builders (they share one step body and one exchange body, so the
sequence-parallel step carries them too), on the transports' exchange alone,
and nothing else changed by them.  CPU only, a toy model, no socket."""

import contextlib
import re
from unittest import mock

import jax
import jax.numpy as jnp
import optax
import pytest

from dpwa_tpu.config import make_local_config
from dpwa_tpu.interpolation import PeerMeta
from dpwa_tpu.parallel.ici import IciTransport
from dpwa_tpu.parallel.mesh import make_mesh
from dpwa_tpu.parallel.stacked import (
    StackedTransport,
    init_stacked_state,
    make_stacked_train_step,
)
from dpwa_tpu.train import init_gossip_state, make_gossip_train_step
from dpwa_tpu.train_sp import make_gossip_sp_train_step, make_sp_mesh
from dpwa_tpu.utils import scopes

N = 4
FORWARD, BACKWARD = "jvp(dpwa.forward)", "transpose(jvp(dpwa.forward))"
# One instruction of HLO text: its opcode and, where it has one, its op_name.
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?\S+ = \S+ ([a-z][a-z-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
BUILDERS = ["stacked", "ici", "sp"]
MESH_BUILDERS = ["ici", "sp"]
SP = 2
FILTERS = [None, "dense"]


def init(key):
    k1, k2 = jax.random.split(key)
    return {
        "conv": jax.random.normal(k1, (3, 3, 1, 4)) * 0.1,
        "dense": {
            "w": jax.random.normal(k2, (4 * 8 * 8, 10)) * 0.1,
            "b": jnp.zeros(10),
        },
    }


def loss_fn(params, batch):
    x, y = batch
    h = jax.lax.conv_general_dilated(
        x, params["conv"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    h = jax.nn.relu(h).reshape(x.shape[0], -1)
    logits = h @ params["dense"]["w"] + params["dense"]["b"]
    return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()


def sp_loss_fn(params, batch):
    """``loss_fn`` as the sequence-parallel step wants it: (sum, count) over
    this rank's block.  A rank holds ``8 / SP`` of an image's rows; the toy
    model pads them back to the height its dense layer was made for."""
    x, y = batch
    x = jnp.pad(x, ((0, 0), (0, 8 - x.shape[1]), (0, 0), (0, 0)))
    return loss_fn(params, (x, y[:, 0])) * x.shape[0], jnp.float32(x.shape[0])


def transport_of(builder):
    cfg = make_local_config(N, schedule="random", pool_size=4, seed=1)
    if builder == "stacked":
        return StackedTransport(cfg)
    mesh = make_mesh(cfg) if builder == "ici" else make_sp_mesh(cfg, SP)
    return IciTransport(cfg, mesh=mesh)


def lowered_step(builder, only=None, overlap=False):
    """The toy step of one builder, lowered on the CPU (four forced devices
    for the mesh, eight for peers x sp); ``only`` exchanges the leaves whose
    path starts with it."""
    transport = transport_of(builder)
    optimizer = optax.sgd(0.1, momentum=0.9)
    params = jax.vmap(init)(jax.random.split(jax.random.key(0), N))
    batch = (jnp.ones((N, 2, 8, 8, 1)), jnp.zeros((N, 2), jnp.int32))
    exchange_filter = None if only is None else (lambda p: p.startswith(only))
    if builder == "stacked":
        state = init_stacked_state(params, optimizer, transport)
        step = make_stacked_train_step(
            loss_fn, optimizer, transport, exchange_filter=exchange_filter,
            overlap=overlap,
        )
    elif builder == "ici":
        state = init_gossip_state(params, optimizer, transport)
        step = make_gossip_train_step(
            loss_fn, optimizer, transport, exchange_filter=exchange_filter,
            overlap=overlap,
        )
    else:
        # Every leaf [n, B, T, ...] with T over sp: a label per rank.
        batch = (batch[0], jnp.zeros((N, 2, SP), jnp.int32))
        state = init_gossip_state(params, optimizer, transport)
        step = make_gossip_sp_train_step(
            sp_loss_fn, optimizer, transport,
            exchange_filter=exchange_filter, overlap=overlap,
        )
    return jax.jit(step).lower(state, batch)


def instructions(hlo_text):
    """[(opcode, op_name or "")] of every instruction of ``hlo_text``."""
    out = []
    for line in hlo_text.splitlines():
        match = INSTRUCTION.match(line)
        if match:
            name = OP_NAME.search(line)
            out.append((match.group(1), name.group(1) if name else ""))
    return out


@pytest.fixture(scope="module")
def lowered():
    cache = {}

    def get(builder, only=None, overlap=False):
        key = (builder, only, overlap)
        if key not in cache:
            cache[key] = lowered_step(builder, only, overlap)
        return cache[key]

    return get


def test_scoped_names_the_call_and_keeps_the_function():
    def f(x, scale=2.0):
        """doc"""
        return x * scale

    g = scopes.scoped("dpwa.test")(f)
    assert (g.__name__, g.__doc__) == ("f", "doc")
    text = jax.jit(g).lower(jnp.ones(3), scale=3.0).as_text(debug_info=True)
    assert "dpwa.test/mul" in text
    assert float(g(jnp.float32(2.0), scale=3.0)) == 6.0
    assert {scopes.FORWARD, scopes.OPTIMIZER, scopes.EXCHANGE} == {
        "dpwa.forward", "dpwa.optimizer", "dpwa.exchange",
    }


@pytest.mark.parametrize("only", FILTERS)
@pytest.mark.parametrize("builder", BUILDERS)
def test_lowered_step_holds_all_four_phases(lowered, builder, only):
    names = {
        name
        for _, name in instructions(
            lowered(builder, only).as_text(dialect="hlo", debug_info=True)
        )
    }
    for part in (FORWARD, BACKWARD, "dpwa.optimizer", "dpwa.exchange"):
        assert any(part in name for name in names), (part, builder, only)
    # The wrapper JAX puts around a scope depends on the map: vmap on one
    # device, none under shard_map.
    wrapped = "vmap(jvp(dpwa.forward))" if builder == "stacked" else (
        "shard_map/jvp(dpwa.forward)"
    )
    assert any(wrapped in name or name.startswith("jvp(") for name in names)
    if builder == "sp":
        # What is the sp step's own stays outside the shared scopes: the
        # reductions over sp are no part of forward, optimizer or exchange.
        reduces = [
            name for op, name in instructions(
                lowered(builder, only).as_text(dialect="hlo", debug_info=True)
            )
            if op == "all-reduce"
        ]
        assert reduces and not any("dpwa." in name for name in reduces)


@pytest.mark.parametrize("only", FILTERS)
@pytest.mark.parametrize("builder", BUILDERS)
def test_matmuls_and_convolutions_lie_under_forward_or_backward(
    lowered, builder, only
):
    found = [
        (op, name)
        for op, name in instructions(
            lowered(builder, only).as_text(dialect="hlo", debug_info=True)
        )
        if op in ("dot", "convolution")
    ]
    # conv forward, its two gradients less the input's (x needs none), the
    # dense layer's forward and two gradients: at least two of each side.
    assert len(found) >= 4
    assert all("dpwa.forward" in name for _, name in found), found
    assert sum("transpose(" in name for _, name in found) >= 2
    assert sum("transpose(" not in name for _, name in found) >= 2
    assert not any(
        "dpwa.optimizer" in name or "dpwa.exchange" in name
        for _, name in found
    )


@pytest.mark.parametrize("overlap", [False, True])
@pytest.mark.parametrize("only", FILTERS)
@pytest.mark.parametrize("builder", MESH_BUILDERS)
def test_every_collective_permute_lies_under_exchange(
    lowered, builder, only, overlap
):
    found = [
        name
        for op, name in instructions(
            lowered(builder, only, overlap).as_text(
                dialect="hlo", debug_info=True
            )
        )
        if op.startswith("collective-permute")
    ]
    assert found and all("dpwa.exchange" in name for name in found), found
    assert all(name.endswith("ppermute") for name in found)


@pytest.mark.parametrize("only", FILTERS)
def test_stacked_partner_gather_lies_under_exchange(lowered, only):
    gathers = [
        name
        for op, name in instructions(
            lowered("stacked", only).as_text(dialect="hlo", debug_info=True)
        )
        if op == "gather"
    ]
    # The partner's replica arrives by gather, a leaf at a time, and so do
    # its clock and loss; the only other gathers pick the labels' logits
    # (a nested jit names its instructions from its own root).
    exchanged = 3 if only is None else 2
    assert sum("dpwa.exchange" in name for name in gathers) == exchanged + 2
    assert all(
        "dpwa.exchange" in name or "dpwa.forward" in name or name == "gather"
        for name in gathers
    ), gathers


@pytest.mark.parametrize("only", FILTERS)
@pytest.mark.parametrize("builder", BUILDERS)
def test_overlap_mode_applies_updates_under_optimizer(lowered, builder, only):
    def last_adds(low):
        names = [
            name for _, name in instructions(
                low.as_text(dialect="hlo", debug_info=True)
            )
        ]
        last = lambda scope: max(
            i for i, name in enumerate(names)
            if name.endswith(scope + "/add") or name.endswith(scope + ")/add")
        )
        return last("dpwa.optimizer"), last("dpwa.exchange")

    # The lowered text defines a value before its use.  Lock step: the
    # merge's add consumes the optimizer's.  Overlap: the updates are added
    # onto the merged tree, under the optimizer's scope and after the merge.
    optimizer, exchange = last_adds(lowered(builder, only))
    assert optimizer < exchange
    optimizer, exchange = last_adds(lowered(builder, only, True))
    assert optimizer > exchange


@pytest.mark.parametrize("builder", BUILDERS)
def test_exchange_alone_carries_the_scope(builder):
    transport = transport_of(builder)
    params = jax.vmap(init)(jax.random.split(jax.random.key(0), N))
    meta = PeerMeta(jnp.ones(N, jnp.float32), jnp.arange(N, dtype=jnp.float32))
    text = transport._exchange.lower(params, meta, jnp.int32(0)).compile().as_text()
    named = [name for _, name in instructions(text) if name]
    moving = "gather" if builder == "stacked" else "ppermute"
    assert any(
        "dpwa.exchange" in name and name.endswith(moving) for name in named
    )
    # Nothing of the exchange program lies outside the scope, bar its
    # parameters and what the map itself adds around the body.
    outside = {name for name in named if "dpwa.exchange" not in name}
    assert all(
        "/" not in name or name.endswith("/shard_map") for name in outside
    ), outside
    assert not any("dpwa.forward" in n or "dpwa.optimizer" in n for n in named)


def arithmetic(compiled_text):
    """Compiled HLO without what names it: metadata, the tables of files,
    functions and stack frames the metadata points into, and identifiers."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", compiled_text)
    if "StackFrames" in text:
        text = text[text.index("\n\n", text.index("StackFrames")):]
    # Instructions and computations are named after the scope they were
    # traced under: what is compared is every instruction's opcode, shape,
    # layout and attributes, in order.
    text = re.sub(r"%[\w.-]+", "%", text)
    return re.sub(r"^HloModule [^\n]*\n", "", text)


@pytest.mark.parametrize("only", FILTERS)
@pytest.mark.parametrize("builder", BUILDERS)
def test_scopes_change_no_arithmetic(lowered, builder, only):
    with_scopes = lowered(builder, only).compile().as_text()
    assert "dpwa.exchange" in with_scopes
    with mock.patch.object(
        jax, "named_scope", lambda name: contextlib.nullcontext()
    ):
        without = lowered_step(builder, only).compile().as_text()
    assert "dpwa." not in without
    assert arithmetic(with_scopes) == arithmetic(without)
    # ... and the comparison can tell two programs apart.
    assert "fusion(" in arithmetic(without)
    assert arithmetic(with_scopes) != arithmetic(
        lowered(builder, only, True).compile().as_text()
    )
