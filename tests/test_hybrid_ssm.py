"""The state-space family through ``models/llama.py``: ``Llama`` at a toy
Jamba shape (14 layers, attention at index 7 without rope, Mamba mixers
elsewhere, a head tied to the embedding) against the plain reference
(``benchmark/references/hybrid_ssm_decoder.py``) on seeded weights, the
frozen base under ``lora_optimizer``, and two stacked peers through the
stacked step against ``benchmark/reference.py``.

Tolerances.  Float32 against float32 differs by the order of summation alone:
1e-4 of rms holds it (seen: some 1e-6) and fails a term left out (the
convolution's bias, an inner norm, ``D``: hundredths and more)."""

import contextlib
import dataclasses
import functools
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference  # noqa: E402
from benchmark.builders import hybrid_ssm_decoder as builder  # noqa: E402
from benchmark.references import hybrid_ssm_decoder as plain  # noqa: E402
from dpwa_tpu.config import make_local_config  # noqa: E402
from dpwa_tpu.models.llama import (  # noqa: E402
    Attention, Llama, LlamaConfig, MambaMixer, lora_filter, lora_optimizer,
)
from dpwa_tpu.ops.cross_entropy import softmax_cross_entropy  # noqa: E402
from tests.yardstick.yardstick_paths import load  # noqa: E402

PUBLISHED = load("benchmark/configs/jamba2-3b-lora.json")
T = 32
# The published pattern at toy widths: 14 layers, attention at index 7.
CONFIG = dict(
    builder.rehearse(PUBLISHED, dict(seq_len=T, per_peer_batch=2))[0],
    hidden_size=32, intermediate_size=48, vocab_size=96,
    num_hidden_layers=14, attn_layer_period=14, attn_layer_offset=7,
)


def model_of(config=CONFIG, **changes) -> Llama:
    model = builder.model_of(config, T)
    return Llama(dataclasses.replace(model.cfg, **changes))


def perturbed(params, key=2):
    """Every leaf moved, so that LoRA B, the biases and the norms' scales
    matter."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(key), len(leaves))
    return treedef.unflatten([
        v + 0.05 * jax.random.normal(k, v.shape, v.dtype)
        for v, k in zip(leaves, keys)
    ])


@pytest.fixture(scope="module")
def seeded():
    tokens = jax.random.randint(
        jax.random.key(0), (2, T), 0, CONFIG["vocab_size"]
    )
    params = perturbed(model_of().init(jax.random.key(1), tokens))
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2))


def paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): leaf for path, leaf in flat}


def adapters(tree):
    return {k: v for k, v in paths(tree).items() if lora_filter(k)}


def loss_of(model):
    return lambda p, tokens, targets: softmax_cross_entropy(
        model.apply(p, tokens), targets
    ).mean()


def reference_loss(params, tokens, targets):
    logits = plain.forward(CONFIG, params, tokens)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return (jax.nn.logsumexp(logits, -1) - picked).mean()


@contextlib.contextmanager
def scan_and_checkpoint(scan: str, policy: bool):
    """The model's scan by the kernels themselves (the Pallas interpreter,
    four chunks of 8 steps, so that a chunk's boundary state is a computed
    one) where ``scan`` is "kernels"; and a Mamba block's ``nn.remat``
    without its policy, as every block had it before, where ``policy`` is
    false."""
    from dpwa_tpu.models import llama
    from dpwa_tpu.ops import ssm

    with contextlib.ExitStack() as stack:
        if scan == "kernels":
            stack.enter_context(
                mock.patch.object(ssm, "selective_scan", ssm.interpreted_scan)
            )
            stack.enter_context(
                mock.patch.object(ssm, "chunk_length", lambda steps: 8)
            )
        if not policy:
            stack.enter_context(mock.patch.object(
                llama, "_checkpoint_policy", lambda cfg, index: None
            ))
        yield


# remat, the scan's path, a Mamba block's checkpoint with its policy
CHECKPOINTS = {
    "True": (True, "plain", True),
    "False": (False, "plain", True),
    "policy-less": (True, "plain", False),
    "kernels": (True, "kernels", True),
    "kernels-policy-less": (True, "kernels", False),
}


@pytest.fixture(scope="module")
def computed(seeded):
    """``case -> (logits, loss, gradients)`` of the model, each case
    computed once for the two tests that read it."""
    params, tokens, targets = seeded

    @functools.cache
    def of(case):
        remat, scan, policy = CHECKPOINTS[case]
        model = model_of(remat=remat)
        with scan_and_checkpoint(scan, policy):
            return model.apply(params, tokens), *jax.value_and_grad(
                loss_of(model)
            )(params, tokens, targets)

    return of


@pytest.fixture(scope="module")
def wanted(seeded):
    params, tokens, targets = seeded
    return plain.forward(CONFIG, params, tokens), *jax.value_and_grad(
        reference_loss
    )(params, tokens, targets)


@pytest.mark.parametrize("case", list(CHECKPOINTS))
def test_the_model_equals_the_reference_logits_loss_and_adapter_gradients(
    computed, wanted, case
):
    logits, loss, grads = computed(case)
    want_logits, want, want_grads = wanted
    assert relative(logits, want_logits) < 1e-4
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got, want_grads = adapters(grads), adapters(want_grads)
    # a and b of: 4 projections x 13 mixers, 4 of the attention layer, 3 of
    # each layer's MLP.
    assert len(got) == 2 * (4 * 13 + 4 + 3 * 14)
    for name, grad in got.items():
        assert relative(grad, want_grads[name]) < 1e-4, name
        assert float(jnp.abs(grad).max()) > 0, name


@pytest.mark.parametrize("case, others", [
    ("True", ("policy-less", "False")), ("kernels", ("kernels-policy-less",)),
])
def test_what_a_mamba_block_keeps_changes_no_bit_of_a_gradient(
    computed, case, others
):
    """A kept ``y`` and kept boundary states are the first pass's own: the
    backward pass reads the values a recomputation would have given it, so
    loss and gradients with the policy, without it and without ``remat``
    are the same arithmetic."""
    for other in others:
        for got, want in zip(
            jax.tree.leaves(computed(case)), jax.tree.leaves(computed(other))
        ):
            np.testing.assert_array_equal(got, want)


def lowered_for_the_chip(fn, *args) -> str:
    """The StableHLO of ``fn`` lowered for a TPU, with the kernels'
    dispatchers answered as the chip, locations (the ``op_name`` a trace
    shows) included.  Lowering needs no chip and no TPU compiler."""
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)
        ).as_text(debug_info=True)


def two_peers_gradient():
    """Two stacked peers' gradient of the loss of a 4-layer hybrid (Mamba,
    Mamba, attention, Mamba) under ``remat``, and shapes to lower it at."""
    config, cell = builder.rehearse(PUBLISHED, dict(
        seq_len=128, per_peer_batch=1, peers=2, exchange_filter="lora",
    ))
    config = dict(
        config, hidden_size=128, num_attention_heads=1, attn_layer_period=4,
        attn_layer_offset=2,
    )
    built = builder.build(config, dict(cell, seq_len=128))
    assert config["assumed"]["remat"]
    shapes = jax.eval_shape(
        jax.vmap(built.init_fn), jax.random.split(jax.random.key(0), 2)
    )
    tokens = jnp.zeros((2, 1, 128), jnp.int32)
    return jax.vmap(jax.grad(built.loss_fn)), shapes, (tokens, tokens)


@pytest.mark.parametrize("policy, recomputed", [
    (True, []), (False, [0, 1, 3]),
])
def test_the_recomputation_runs_the_forward_kernel_only_where_nothing_is_kept(
    policy, recomputed
):
    """Two stacked peers' gradients of a 4-layer hybrid (Mamba, Mamba,
    attention, Mamba) under ``remat``: the forward scan kernel once a Mamba
    layer in the forward pass and in no layer's recomputation (in every
    Mamba layer's where the blocks' ``nn.remat`` has no policy, as before),
    the backward kernel once a Mamba layer."""
    import re

    with scan_and_checkpoint("chip", policy):
        text = lowered_for_the_chip(*two_peers_gradient())
    calls = re.findall(
        r'loc\("([^"]*)/layer_(\d)/[^"]*dpwa_selective_scan_(fwd|bwd)/', text
    )
    where = lambda kernel, inside: sorted(
        int(layer) for scope, layer, k in calls
        if k == kernel and ("rematted_computation" in scope) == inside
    )
    assert where("fwd", False) == [0, 1, 3]
    assert where("fwd", True) == recomputed
    assert where("bwd", False) == [0, 1, 3] and where("bwd", True) == []
    assert all(
        ("transpose(jvp(" in scope) == (k == "bwd" or "remat" in scope)
        for scope, _, k in calls
    )
    # What is kept is saved as it is: no pass over it to round it to its
    # own type (``ops/ssm._named_bits``).
    assert "reduce_precision" not in text


def without_names(names=None):
    """``jax.named_scope`` as a null context for ``names`` (None: for every
    name, flax's own among them), so that what is traced carries none."""
    named_scope = jax.named_scope
    return mock.patch.object(
        jax, "named_scope",
        lambda name: contextlib.nullcontext()
        if names is None or name in names else named_scope(name),
    )


def test_the_mixers_parts_carry_their_names_in_the_lowered_loss():
    """Forward, recomputed and backward alike: each projection's product
    under ``dpwa.ssm.proj`` with the module's own name next, the
    convolution's two kernels (PR 56; the taps and silu as XLA's own before)
    and ``z``'s slice under ``dpwa.ssm.conv``, the norms and softplus under
    ``dpwa.ssm.dt``, the gate's product under ``dpwa.ssm.gate``; every one
    of them inside ``dpwa.ssm``, none around or inside ``dpwa.ssm.scan``,
    whose own instructions are the ones it had without the four."""
    import re

    from dpwa_tpu.utils import scopes

    parts = scopes.SSM_PARTS
    assert not any(scopes.SSM_SCAN in name for name in parts)
    locations = lambda text: set(re.findall(r'loc\("([^"]*)"', text))
    names = locations(lowered_for_the_chip(*two_peers_gradient()))
    tails = lambda part: {
        n.split(f"/{scopes.SSM}/{part}/", 1)[1] for n in names
        if f"/{scopes.SSM}/{part}/" in n
    }
    for module in ("in_proj", "x_proj", "dt_proj", "out_proj"):
        products = {
            n for n in names
            if "/mamba/" in n and n.endswith(module + "/dot_general")
        }
        passes = {
            ("transpose(" in n, "rematted_computation" in n) for n in products
        }
        assert passes == {(False, False), (True, False), (True, True)}, module
        assert all(
            f"/{scopes.SSM}/{parts.proj}/{module}/" in n for n in products
        ), module
    assert tails(parts.proj) and all(
        t.split("/")[0] in ("in_proj", "x_proj", "dt_proj", "out_proj")
        for t in tails(parts.proj)
    )
    conv = tails(parts.conv)
    # The kernels' calls are jitted, lowered once and called a layer: the
    # call carries the mixer's names, the kernel its own.
    assert {"slice", "jit(conv_silu_fwd)"} <= conv
    assert any(t.endswith("jit(conv_silu_bwd)") for t in conv)
    assert {
        "dpwa_conv_silu_fwd/pallas_call", "dpwa_conv_silu_bwd/pallas_call",
    } <= names
    assert any("pad" in t for t in conv)
    step_size = tails(parts.dt)
    assert {"jit(softplus)", "add", "exp", "neg"} <= step_size
    assert {t.split("/")[0] for t in step_size if "_norm" in t} == {
        "dt_norm", "b_norm", "c_norm",
    }
    assert {"mul", "jit(silu)"} <= tails(parts.gate)
    # Nothing of a part stands outside the mixer's own name, and the three
    # inner norms, softplus and silu stand nowhere else in a mixer.
    for n in names:
        for part in parts:
            assert (part in n) == (f"/{scopes.SSM}/{part}/" in n), n
        if "/mamba/" in n and any(
            key in n for key in ("_norm/", "softplus", "silu")
        ):
            assert any(f"/{part}/" in n for part in parts), n
    scan = {n for n in names if scopes.SSM_SCAN in n}
    assert scan and not any(part in n for n in scan for part in parts)
    with without_names(tuple(parts)):
        before = locations(lowered_for_the_chip(*two_peers_gradient()))
    assert not any(part in n for n in before for part in parts)
    assert {n for n in before if scopes.SSM_SCAN in n} == scan


def test_the_names_move_no_bit_of_a_mixers_output_or_gradients():
    cfg = model_of().cfg
    mixer = MambaMixer(cfg)
    u = jax.random.normal(jax.random.key(3), (2, T, cfg.d_model))
    params = perturbed(mixer.init(jax.random.key(4), u))

    def computed():
        # New functions a call: nothing traced under the other names is
        # found again.
        forward = lambda p, u: mixer.apply(p, u)
        loss = lambda p, u: jnp.sum(jnp.sin(forward(p, u)))
        text = jax.jit(forward).lower(params, u).as_text(debug_info=True)
        return "dpwa.ssm" in text, jax.jit(forward)(params, u), jax.jit(
            jax.grad(loss, argnums=(0, 1))
        )(params, u)

    with_names, *named = computed()
    with without_names():
        with_none, *bare = computed()
    assert with_names and not with_none
    assert float(jnp.abs(named[0]).max()) > 0
    for got, want in zip(jax.tree.leaves(named), jax.tree.leaves(bare)):
        np.testing.assert_array_equal(got, want)


def pallas_calls(jaxpr):
    """Every ``pallas_call`` equation, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from pallas_calls(sub)


def test_both_scan_kernels_carry_the_rules_block_and_a_limit_over_their_shapes():
    """At the published shape (2 peers x 1 x 4096 x 5120, ``N`` 16; shapes
    alone, no chip and no TPU compiler) both scan kernels are called with
    the channel block ``ops/ssm.channel_block`` gives, 1,024, and ask Mosaic
    for more VMEM than their own shapes come to: the scratch each call
    really has (read off the traced call, not off ``ops/ssm``'s reckoning)
    plus every operand's and result's block twice.  A scratch buffer added
    or widened without the reckoning following it fails here and not on the
    chip; so does one that outgrows the VMEM a v5e core has."""
    import re

    from dpwa_tpu.ops import ssm

    steps, channels, states = 4096, 5120, 16
    shaped = jax.ShapeDtypeStruct
    args = (
        shaped((2, 1, steps, channels), jnp.bfloat16),
        shaped((2, 1, steps, channels), jnp.float32),
        shaped((2, channels, states), jnp.float32),
        shaped((2, 1, steps, states), jnp.bfloat16),
        shaped((2, 1, steps, states), jnp.bfloat16),
        shaped((2, channels), jnp.float32),
    )

    def loss(*a):
        return jax.vmap(ssm.selective_scan)(*a).astype(jnp.float32).sum()

    grad = jax.grad(loss, argnums=tuple(range(6)))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        calls = {
            eqn.params["name"]: eqn.params
            for eqn in pallas_calls(jax.make_jaxpr(grad)(*args).jaxpr)
        }
    text = lowered_for_the_chip(grad, *args)
    assert sorted(calls) == [
        "dpwa_selective_scan_bwd", "dpwa_selective_scan_fwd",
    ]
    chunk, block = ssm.chunk_length(steps), ssm.channel_block(channels)
    assert (chunk, block) == (128, 1024)
    size = lambda shape: int(np.prod(shape))
    asked = {}
    for name, reckoned in (
        ("dpwa_selective_scan_fwd", ssm._forward_scratch),
        ("dpwa_selective_scan_bwd", ssm._backward_scratch),
    ):
        mapping = calls[name]["grid_mapping"]
        assert mapping.grid == (2, channels // block, steps // chunk)
        blocks = [
            (
                [d.block_size for d in b.block_shape if hasattr(d, "block_size")],
                b.array_aval.dtype,
            )
            for b in mapping.block_mappings
        ]
        # x, delta (and dy, dx, ddelta) move a chunk of the block's channels.
        assert blocks[0] == ([chunk, block], jnp.bfloat16)
        assert blocks[1] == ([chunk, block], jnp.float32)
        assert all(shape[-1] in (block, chunk) for shape, _ in blocks)
        scratch = [
            v.aval for v in
            calls[name]["jaxpr"].invars[-mapping.num_scratch_operands:]
        ]
        assert [a.shape for a in scratch] == reckoned(chunk, states, block)
        held = sum(size(a.shape) * a.dtype.itemsize for a in scratch) + 2 * sum(
            size(shape) * dtype.itemsize for shape, dtype in blocks
        )
        limit = calls[name]["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        # A v5e core has 128 MiB of VMEM; Mosaic's default scope is 16.
        assert held < limit <= 128 * 2 ** 20, (name, held, limit)
        assert limit == ssm.vmem_limit(held) >= ssm.DEFAULT_VMEM_LIMIT
        asked[name] = (held, limit)
    # The states of a chunk are most of it: (chunk + 1) x N x block float32.
    assert asked["dpwa_selective_scan_bwd"][0] > 129 * 16 * 1024 * 4
    # What was asked reaches the compiler: the lowered calls carry it, as
    # the size of their scoped memory.
    assert sorted(
        int(n) for n in
        re.findall(r'scoped_memory_configs[^\]]*?size\\22: (\d+)', text)
    ) == sorted(limit for _, limit in asked.values())


@pytest.mark.parametrize("changes, keeping", [
    (dict(), []),  # every accepted decoder: attention in every layer
    (dict(attn_layer_period=14, attn_layer_offset=7, mamba_dt_rank=8),
     [0, 1, 2, 3, 4, 5, 6, 8, 9, 10, 11, 12, 13]),
    (dict(attn_layer_period=2, attn_layer_offset=0, mamba_dt_rank=8),
     [1, 3, 5, 7, 9, 11, 13]),
])
def test_only_a_mamba_block_has_a_checkpoint_policy(
    changes, keeping
):
    from dpwa_tpu.models.llama import _checkpoint_policy

    cfg = LlamaConfig(n_layers=14, remat=True, rope_theta=1e4, **changes)
    assert [
        i for i in range(14) if _checkpoint_policy(cfg, i) is not None
    ] == keeping


@pytest.mark.parametrize("left_out", [
    "conv_bias", "dt_bias", "D", "b_norm", "c_norm", "dt_norm",
])
def test_a_leaf_left_out_is_outside_the_tolerance(seeded, left_out):
    """The comparison can tell: with one mixer leaf of layer 0 neutral in
    the program (a zero bias, a zero ``D``, a norm's scale of one) the
    logits leave the 1e-4 by two orders."""
    params, tokens, _ = seeded
    mixer = dict(params["params"]["layer_0"]["mamba"])
    if left_out.endswith("_norm"):
        mixer[left_out] = dict(scale=jnp.ones_like(mixer[left_out]["scale"]))
    else:
        mixer[left_out] = jnp.zeros_like(mixer[left_out])
    changed = {"params": dict(
        params["params"],
        layer_0=dict(params["params"]["layer_0"], mamba=mixer),
    )}
    assert relative(
        model_of().apply(changed, tokens), plain.forward(CONFIG, params, tokens)
    ) > 1e-2


def test_layer_7_of_14_is_attention_and_the_others_are_mixers(seeded):
    params, _, _ = seeded
    layers = params["params"]
    for i in range(14):
        layer = layers[f"layer_{i}"]
        assert ("attn" in layer) == (i == 7), i
        assert ("mamba" in layer) == (i != 7), i
        assert plain.is_attention_layer(CONFIG, i) == (i == 7)
        assert model_of().cfg.is_attention_layer(i) == (i == 7)
    mixer = layers["layer_0"]["mamba"]
    assert set(mixer) == {
        "in_proj", "x_proj", "dt_proj", "out_proj", "conv_kernel",
        "conv_bias", "dt_bias", "A_log", "D", "dt_norm", "b_norm", "c_norm",
    }
    assert "bias" not in mixer["dt_proj"]  # LoRADense has none: dt_bias is it


@pytest.mark.parametrize("rope_theta, moves", [(None, False), (1e4, True)])
def test_the_attention_layer_carries_no_rope(seeded, rope_theta, moves):
    """Without ``rope_theta`` shifting ``positions`` changes nothing; with
    one (every accepted decoder) it does."""
    params, _, _ = seeded
    cfg = model_of(rope_theta=rope_theta).cfg
    weights = {"params": params["params"]["layer_7"]["attn"]}
    x = jax.random.normal(jax.random.key(3), (2, T, cfg.d_model))
    at = lambda positions: Attention(cfg).apply(weights, x, positions)
    here, shifted = at(jnp.arange(T)), at(3 * jnp.arange(T) + 5)
    assert bool((here == shifted).all()) != moves


def test_the_head_is_the_embedding(seeded):
    params, tokens, _ = seeded
    assert "lm_head" not in params["params"]
    model = model_of()
    logits = model.apply(params, tokens)
    assert logits.dtype == jnp.float32
    assert logits.shape == (2, T, CONFIG["vocab_size"])
    # One row of the embedding scaled: that token's column of logits scales.
    embedding = params["params"]["embed"]["embedding"]
    unseen = int(np.setdiff1d(np.arange(CONFIG["vocab_size"]), tokens)[0])
    scaled = {"params": dict(
        params["params"],
        embed=dict(embedding=embedding.at[unseen].multiply(2.0)),
    )}
    got = model.apply(scaled, tokens)
    np.testing.assert_allclose(
        got[..., unseen], 2.0 * logits[..., unseen], rtol=1e-5, atol=1e-6
    )
    others = np.arange(CONFIG["vocab_size"]) != unseen
    np.testing.assert_allclose(
        got[..., others], logits[..., others], rtol=1e-5, atol=1e-6
    )


def test_the_published_initial_values():
    cfg = model_of().cfg
    mixer = MambaMixer(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8, cfg.d_model))
    )["params"]
    n, e = cfg.mamba_d_state, cfg.mamba_expand * cfg.d_model
    np.testing.assert_allclose(
        np.exp(mixer["A_log"]), np.broadcast_to(np.arange(1, n + 1), (e, n)),
        rtol=1e-6,
    )
    assert bool((mixer["D"] == 1).all())
    dt = jax.nn.softplus(mixer["dt_bias"])
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.001
    for name in ("A_log", "D", "dt_bias"):
        assert mixer[name].dtype == jnp.float32


def test_base_leaves_are_born_in_param_dtype_but_the_scan_parameters():
    params = jax.eval_shape(
        model_of(param_dtype=jnp.bfloat16).init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32),
    )
    for name, leaf in paths(params).items():
        wide = lora_filter(name) or any(
            key in name for key in ("A_log", "['D']", "dt_bias")
        )
        assert leaf.dtype == (jnp.float32 if wide else jnp.bfloat16), name


@pytest.mark.parametrize("changes, message", [
    (dict(sp_axis="sp"), "sequence-parallel"),
    (dict(attn_layer_offset=14), "attn_layer_offset"),
    (dict(mamba_dt_rank=0), "four sizes"),
])
def test_what_is_not_built_is_refused(changes, message):
    with pytest.raises(ValueError, match=message):
        model_of(**changes)


def test_the_defaults_are_todays_behaviour():
    cfg = LlamaConfig()
    assert cfg.attn_layer_period == 0 and not cfg.tie_embeddings
    assert cfg.rope_theta == 500000.0
    assert all(cfg.is_attention_layer(i) for i in range(8))


def test_lora_optimizer_leaves_every_base_leaf_bit_identical(seeded):
    params, tokens, targets = seeded
    model = model_of()
    optimizer = lora_optimizer(optax.adam(1e-2), params)
    opt_state = optimizer.init(params)
    new = params

    @jax.jit
    def step(p, s):
        grads = jax.grad(loss_of(model))(p, tokens, targets)
        updates, s = optimizer.update(grads, s, p)
        return optax.apply_updates(p, updates), s

    for _ in range(3):
        new, opt_state = step(new, opt_state)
    before, after = paths(params), paths(new)
    frozen = [k for k in before if not lora_filter(k)]
    assert any("A_log" in k for k in frozen) and len(frozen) > 100
    for name in before:
        same = bool((before[name] == after[name]).all())
        assert same != lora_filter(name), name


def test_two_stacked_peers_match_the_references_local_update():
    """The stacked step (``vmap`` over peers, the scan's vmap rule) against
    ``benchmark/reference.py``'s loop over peers, as ``run.py`` checks it."""
    from dpwa_tpu.parallel.stacked import (
        StackedTransport, init_stacked_state, make_stacked_train_step,
    )
    from dpwa_tpu.train import init_params_per_peer

    config, cell = builder.rehearse(PUBLISHED, dict(
        seq_len=T, per_peer_batch=2, peers=2, exchange_filter="lora",
    ))
    built = builder.build(config, dict(cell, seq_len=T))
    transport = StackedTransport(make_local_config(2, schedule="ring"))
    optimizer = built.make_optimizer(
        jax.eval_shape(built.init_fn, jax.random.key(0))
    )
    stacked = init_params_per_peer(built.init_fn, jax.random.key(4), 2)
    state = init_stacked_state(stacked, optimizer, transport)
    step = make_stacked_train_step(
        built.loss_fn, optimizer, transport,
        exchange_filter=built.exchange_filter,
    )
    tokens = jax.random.randint(
        jax.random.key(5), (2, 2, T + 1), 0, config["vocab_size"]
    )
    batch = tokens[..., :-1], tokens[..., 1:]
    for _ in range(2):  # so that LoRA B has left zero
        state, _, _ = step(state, batch)
    local = reference.make_local_update(
        built.loss_fn, optimizer, built.exchange_filter
    )
    u_leaves, moved = local(state.params, state.opt_state, batch)
    jax.block_until_ready(u_leaves)
    state, losses, info = step(state, batch)
    assert not reference.check_info(
        info.partner, info.alpha, info.participated, 0.5
    )
    verdict = reference.compare(
        state.params, reference.merge(u_leaves, info.partner, info.alpha),
        moved, info.alpha, built.exchange_filter,
    )
    assert verdict.ok, verdict.reasons
    assert verdict.worst_ratio < 0.1 and bool(jnp.isfinite(losses).all())
    error, size = reference.make_model_check(
        built.apply_fn, built.reference_forward, lambda b: b[0][:1, :T],
    )(state.params, batch)
    assert float(error) < 1e-4 * float(size)


# ---- what the accepted decoders computed before, they compute now

# The first 16 hex digits of the SHA-256 of the lowered program's text
# (StableHLO, which carries no address and does not depend on the machine),
# taken on the commit before this family (b07feda) by the same lines.  The
# same program is the same arithmetic, bit for bit.  A new JAX prints other
# text: pin them again from a tree that is known good.  Every program with a
# rope in it (all but ``LoRADense``) was pinned again at PR 43, which wrote
# ``rope`` without its strided slices: ``tests/test_evabyte.py`` holds the
# new body to the old one's values, bit for bit.  The two ``loss_grad``
# programs that run ``ops/moe._all_rows`` were pinned again at PR 50, whose
# combine keeps the sorted rows (the OLMoE one, and A.X-K1's at this toy
# shape, where a share has no row cap; the forward programs did not move):
# ``tests/test_moe.py`` holds the new combine to the old one's values.
# The same two configurations' four programs were pinned again at PR 51,
# which put the frozen down projection and the combine under one gradient
# rule: forward the same operations, the down adapter's A side traced before
# the frozen product where it was traced after (``tests/test_moe.py`` holds
# the layer's and the toy model's results to the bit, the gradients to the
# two rules').
PROGRAMS_BEFORE = {
    "LoRADense": "af720f95b366931e",
    "Attention": "a4244af0960dfaa8",
    "Block": "6c5c87e2875291b2",
    "Llama": "622e73bbd68313ff",
    "mistral-7b-v0.3-lora": "72c81c1f1b155b21",
    "mistral-7b-v0.3-lora.loss_grad": "7dceb0d6fdeaaf08",
    "olmoe-1b-7b-0125-lora": "f33098cd71527928",
    "olmoe-1b-7b-0125-lora.loss_grad": "2b122a44eb5a2300",
    "axk1-lora": "9c2a1aa8e10913ce",
    "axk1-lora.loss_grad": "8adc1058a03a5797",
}


def program_digest(fn, *args):
    import hashlib

    text = jax.jit(fn).lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", ["LoRADense", "Attention", "Block", "Llama"])
def test_a_module_of_the_accepted_decoders_lowers_to_the_program_it_did(name):
    from dpwa_tpu.models.llama import Block, LoRADense

    cfg = LlamaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=48, lora_rank=4, rope_theta=1e4,
    )
    x, positions = jnp.zeros((2, 16, 32)), jnp.arange(16)
    module, inputs = dict(
        LoRADense=(LoRADense(24, 4, 8.0), (x,)),
        Attention=(Attention(cfg), (x, positions)),
        Block=(Block(cfg, 1), (x, positions)),
        Llama=(Llama(cfg), (jnp.zeros((2, 16), jnp.int32),)),
    )[name]
    shapes = jax.eval_shape(module.init, jax.random.key(0), *inputs)
    assert program_digest(module.apply, shapes, *inputs) == PROGRAMS_BEFORE[name]


@pytest.mark.parametrize("name", [
    "mistral-7b-v0.3-lora", "olmoe-1b-7b-0125-lora", "axk1-lora",
])
def test_an_accepted_configuration_lowers_to_the_programs_it_did(name):
    """Forward and the loss's gradient of each accepted decoder
    configuration at its builder's toy shape."""
    import importlib

    from tests.yardstick.yardstick_paths import MANIFEST

    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    config = load(entry["file"])
    family = importlib.import_module("benchmark.builders." + config["family"])
    toy, cell = family.rehearse(config, dict(
        seq_len=64, per_peer_batch=2, peers=2, exchange_filter="lora",
    ))
    built = family.build(toy, cell)
    shapes = jax.eval_shape(built.init_fn, jax.random.key(0))
    tokens = jnp.zeros((2, cell["seq_len"]), jnp.int32)
    assert program_digest(built.apply_fn, shapes, tokens) == PROGRAMS_BEFORE[name]
    assert program_digest(
        jax.grad(built.loss_fn), shapes, (tokens, tokens)
    ) == PROGRAMS_BEFORE[name + ".loss_grad"]
