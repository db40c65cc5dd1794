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


# ---- the decoder block names its parts (``models/llama.py``)

# configuration -> what its builder's toy shape keeps of it.
DECODERS = {
    "mistral-7b-v0.3-lora": "plain attention, dense layers, an untied head",
    "olmoe-1b-7b-0125-lora": "QK-norm, expert layers",
    "axk1-lora": "latent attention, a leading dense layer, a shared expert",
    "jamba2-3b-lora": "remat, mixers and one attention layer, a tied head",
}
BLOCK_NAMES = (scopes.ATTN_GQA, scopes.MLP, scopes.HEAD)
MOE_NAMES = {scopes.MOE_ROUTE, scopes.MOE_EXPERTS, scopes.MOE_SHARED}


def decoder_loss(name):
    """``(value_and_grad of the loss under dpwa.forward, the builder's init,
    a batch)`` of a decoder configuration at its builder's toy shape."""
    import importlib

    from tests.yardstick.yardstick_paths import MANIFEST, load

    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    config = load(entry["file"])
    family = importlib.import_module("benchmark.builders." + config["family"])
    toy, cell = family.rehearse(config, dict(
        seq_len=64, per_peer_batch=2, peers=2, exchange_filter="lora",
    ))
    built = family.build(toy, cell)
    tokens = jax.random.randint(
        jax.random.key(3), (2, cell["seq_len"] + 1), 0, toy["vocab_size"]
    )
    fn = jax.jit(jax.value_and_grad(scopes.scoped_loss(built.loss_fn)))
    return fn, built.init_fn, (tokens[:, :-1], tokens[:, 1:])


@pytest.fixture(scope="module")
def decoder_dots():
    """name -> the path components of every ``dot``'s op_name in the lowered
    loss and gradient."""
    cache = {}

    def get(name):
        if name not in cache:
            fn, init_fn, batch = decoder_loss(name)
            shapes = jax.eval_shape(init_fn, jax.random.key(0))
            text = fn.lower(shapes, batch).as_text(dialect="hlo", debug_info=True)
            cache[name] = [
                op_name.split("/") for op, op_name in instructions(text)
                if op == "dot"
            ]
        return cache[name]

    return get


def under(parts, module, leaves):
    """Whether an op_name's components hold flax module ``module`` with one
    of ``leaves`` right below it."""
    return any(
        a == module and b in leaves for a, b in zip(parts, parts[1:])
    )


@pytest.mark.parametrize("name", DECODERS)
def test_a_dense_feed_forward_lies_under_mlp_and_no_expert_does(
    decoder_dots, name
):
    dots = decoder_dots(name)
    dense = [p for p in dots if under(p, "mlp", ("w_gate", "w_up", "w_down"))]
    named = [p for p in dots if scopes.MLP in p]
    experts = [p for p in dots if MOE_NAMES & set(p)]
    shared = [p for p in dots if "shared" in p]
    # The module's own dots, its adapters' included, and nothing else.
    assert all(scopes.MLP in p for p in dense)
    assert all("mlp" in p[p.index(scopes.MLP):] for p in named)
    assert not any(scopes.MLP in p for p in experts + shared)
    assert all(scopes.MOE_SHARED in p for p in shared)
    assert bool(dense) == (name != "olmoe-1b-7b-0125-lora")
    assert bool(experts) == (name in ("olmoe-1b-7b-0125-lora", "axk1-lora"))
    assert bool(shared) == (name == "axk1-lora")
    if name == "axk1-lora":  # the leading dense layer alone
        assert {p[p.index(scopes.MLP) - 1] for p in named} == {"layer_0"}
    # Forward and backward carry the name alike.
    assert not named or {True, False} == {
        any("transpose(" in part for part in p) for p in named
    }


@pytest.mark.parametrize("name", DECODERS)
def test_plain_attention_lies_under_gqa_and_latent_attention_does_not(
    decoder_dots, name
):
    dots = decoder_dots(name)
    attention = [p for p in dots if "attn" in p]
    named = [p for p in dots if scopes.ATTN_GQA in p]
    assert attention
    if name == "axk1-lora":
        assert not named
        assert all(scopes.ATTN_LATENT in p for p in attention)
        return
    assert not any(scopes.ATTN_LATENT in p for p in dots)
    assert all(scopes.ATTN_GQA in p for p in attention)
    assert all("attn" in p[p.index(scopes.ATTN_GQA):] for p in named)
    for leaf in ("wq", "wk", "wv", "wo"):
        found = [p for p in dots if under(p, "attn", (leaf,))]
        assert found and all(scopes.ATTN_GQA in p for p in found), leaf
    assert not any(scopes.MLP in p or MOE_NAMES & set(p) for p in named)


@pytest.mark.parametrize("name", DECODERS)
def test_the_head_lies_under_head_tied_or_not(decoder_dots, name):
    dots = decoder_dots(name)
    named = [p for p in dots if scopes.HEAD in p]
    tied = name == "jamba2-3b-lora"
    # The projection to the vocabulary is the one dot right under the model;
    # forward, and backward to the stream (a tied table is frozen here too).
    assert len(named) >= 2
    assert all(p[p.index(scopes.HEAD) - 1] == "Llama" for p in named)
    assert all(("lm_head" in p) != tied for p in named)
    assert all(scopes.HEAD in p for p in dots if "lm_head" in p)
    others = set(BLOCK_NAMES[:2]) | MOE_NAMES | {
        scopes.ATTN_LATENT, scopes.SSM, scopes.LOSS
    }
    assert not any(others & set(p) for p in named)
    assert not any("final_norm" in p for p in named)


def test_the_names_appear_in_what_a_checkpoint_recomputes(decoder_dots):
    again = [
        p for p in decoder_dots("jamba2-3b-lora")
        if "rematted_computation" in p
    ]
    for name in (scopes.ATTN_GQA, scopes.MLP, scopes.SSM):
        inside = [p for p in again if name in p]
        assert inside and all(
            p.index("rematted_computation") < p.index(name) for p in inside
        ), name
    # The head is outside every block's checkpoint.
    assert not any(scopes.HEAD in p for p in again)
    assert not any(
        "rematted_computation" in p
        for p in decoder_dots("mistral-7b-v0.3-lora")
    )


@pytest.mark.parametrize("name", DECODERS)
def test_block_names_change_no_arithmetic(name):
    """Loss and gradients equal to the bit, and the compiled program the
    same, with the three names of the block patched to no-ops."""
    named_scope = jax.named_scope

    def compiled_and_run():
        fn, init_fn, batch = decoder_loss(name)
        params = jax.jit(init_fn)(jax.random.key(0))
        compiled = fn.lower(params, batch).compile()
        return compiled.as_text(), compiled(params, batch)

    text, (loss, grads) = compiled_and_run()
    assert scopes.HEAD in text and any(n in text for n in BLOCK_NAMES[:2])
    with mock.patch.object(
        jax, "named_scope",
        lambda n: contextlib.nullcontext() if n in BLOCK_NAMES
        else named_scope(n),
    ):
        bare_text, (bare_loss, bare_grads) = compiled_and_run()
    assert not any(n in bare_text for n in BLOCK_NAMES)
    assert "dpwa.forward" in bare_text
    assert arithmetic(text) == arithmetic(bare_text)
    assert float(loss) == float(bare_loss) and jnp.isfinite(loss)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(bare_grads)):
        assert bool((got == want).all())
