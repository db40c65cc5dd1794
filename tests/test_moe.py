"""The sparse-expert layer (``ops/moe.py``, ``models/llama.py`` MoE /
QK-norm) against its plain reference (``benchmark/references/moe_decoder.py``)
at toy sizes on the CPU: experts 8, top 2, width 64."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import MODEL_TOLERANCE  # noqa: E402
from benchmark.references import moe_decoder as plain  # noqa: E402
from dpwa_tpu.models.llama import (  # noqa: E402
    Llama, LlamaConfig, lora_filter, lora_optimizer, moe_loss, routing_of,
)
from dpwa_tpu.ops import moe  # noqa: E402

E, K, D, F, V, T = 8, 2, 64, 64, 128, 32
CONFIG = dict(
    hidden_size=D, intermediate_size=F, num_attention_heads=4,
    num_key_value_heads=4, num_experts=E, num_experts_per_tok=K,
    num_hidden_layers=2, vocab_size=V, rms_norm_eps=1e-5, rope_theta=10000,
    assumed=dict(lora=dict(rank=4, alpha=16.0), router_aux_loss_coef=0.01),
)


def model_of(dtype=jnp.float32, **changes):
    fields = dict(
        vocab_size=V, d_model=D, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=F,
        max_seq_len=T, rope_theta=10000.0, lora_rank=4, lora_alpha=16.0,
        dtype=dtype, n_experts=E, n_experts_per_tok=K, qk_norm=True,
        router_aux_loss_coef=0.01,
    )
    return Llama(LlamaConfig(**{**fields, **changes}))


@pytest.fixture(scope="module")
def seeded():
    """(params, tokens, targets): every leaf perturbed, so that LoRA B and
    the norms' scales matter to a comparison."""
    tokens = jax.random.randint(jax.random.key(0), (2, T), 0, V)
    params = model_of().init(jax.random.key(1), tokens)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    params = treedef.unflatten([
        v + 0.05 * jax.random.normal(k, v.shape, v.dtype)
        for v, k in zip(leaves, keys)
    ])
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2))


def adapters(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {
        jax.tree_util.keystr(path): leaf for path, leaf in flat
        if lora_filter(jax.tree_util.keystr(path))
    }


def test_float32_equals_the_reference_with_its_own_routing(seeded):
    params, tokens, targets = seeded
    model = model_of()
    assert relative(
        model.apply(params, tokens), plain.forward(CONFIG, params, tokens)
    ) < 1e-4
    loss, grads = jax.value_and_grad(
        lambda p: moe_loss(model, p, tokens, targets)
    )(params)
    want, want_grads = jax.value_and_grad(
        lambda p: plain.loss(CONFIG, p, tokens, targets)
    )(params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    got, want_grads = adapters(grads), adapters(want_grads)
    assert len(got) == 2 * (4 + 3) * 2  # a and b, 4 + 3 projections, 2 layers
    for name, grad in got.items():
        assert relative(grad, want_grads[name]) < 1e-4, name
        assert float(jnp.abs(grad).max()) > 0, name


def test_load_balancing_term_is_one_when_uniform_and_is_in_the_loss(seeded):
    counts = jnp.full((2, E), 10)
    assert float(moe.load_balancing_loss(counts, jnp.full((2, E), 1 / E))) == (
        pytest.approx(1.0)
    )
    params, tokens, targets = seeded
    with_term = moe_loss(model_of(), params, tokens, targets)
    without = moe_loss(
        model_of(router_aux_loss_coef=0.0), params, tokens, targets
    )
    # Skewed routing reads above 1; the coefficient is 0.01.
    assert 0.01 <= float(with_term - without) < 0.01 * E


def test_bfloat16_with_verified_routing_is_inside_the_tolerance(seeded):
    params, tokens, _ = seeded
    model = model_of(jnp.bfloat16)
    logits, sown = model.apply(params, tokens, mutable=["intermediates"])
    routing = routing_of(sown)["experts"]
    want, details = plain.forward_with_routing(CONFIG, params, tokens, routing)
    error = relative(logits, want)
    assert 1e-5 < error < MODEL_TOLERANCE
    # The program's sets pass at ROUTING_EPS, and by a margin.
    assert float(details["margin"].max()) < plain.ROUTING_EPS / 2


def test_a_set_that_is_not_a_top_k_is_refused(seeded):
    params, tokens, _ = seeded
    own = plain.forward_with_routing(CONFIG, params, tokens)[1]["logits"]
    honest = jax.lax.top_k(own, K)[1]
    assert bool(jnp.all(jnp.isfinite(
        plain.forward(CONFIG, params, tokens, honest)
    )))
    # One token of layer 1 takes its worst expert in place of its second.
    wrong = honest.at[1, 5, 1].set(jnp.argmin(own[1, 5]))
    assert not bool(jnp.all(jnp.isfinite(
        plain.forward(CONFIG, params, tokens, wrong)
    )))
    # The same expert twice is not a set of k.
    twice = honest.at[0, 3, 1].set(honest[0, 3, 0])
    assert not bool(jnp.all(jnp.isfinite(
        plain.forward(CONFIG, params, tokens, twice)
    )))


def _renormalised(route):
    def routed(x, router_kernel, k, *gate):
        weights, experts, logits = route(x, router_kernel, k, *gate)
        return weights / weights.sum(-1, keepdims=True), experts, logits

    return routed


def _with_capacity(route, factor=1.0):
    """A capacity-factor dispatch: an expert takes its first ``factor x N x
    k / E`` assignments in token order and the overflow is dropped."""

    def routed(x, router_kernel, k, *gate):
        weights, experts, logits = route(x, router_kernel, k, *gate)
        n_experts = logits.shape[-1]
        capacity = int(factor * experts.size / n_experts)
        one_hot = jax.nn.one_hot(experts.reshape(-1), n_experts, dtype=jnp.int32)
        position = (jnp.cumsum(one_hot, 0) * one_hot).sum(-1).reshape(experts.shape)
        return jnp.where(position <= capacity, weights, 0.0), experts, logits

    return routed


def _skewed(params):
    """The router's first two columns tripled: uneven routing, so that a
    capacity of the mean overflows."""
    return jax.tree_util.tree_map_with_path(
        lambda path, v: v.at[:, :2].multiply(3.0)
        if "router" in jax.tree_util.keystr(path) else v, params,
    )


def _up_reads_gates_columns(gate_and_up):
    """The merged A side with up's slice taken at gate's columns of
    ``down_cat``: up's adapter sees ``rows A_gate``."""

    def projections(rows, w_gate, w_up, *args):
        return gate_and_up(rows, w_gate, (w_up[0], w_gate[1], w_up[2]), *args)

    return projections


VARIANTS = ["top_7_of_8", "renormalised_weights", "no_qk_norm",
            "capacity_drops_overflow", "experts_without_adapters",
            "up_adapter_from_gates_columns"]


@pytest.mark.parametrize("variant", VARIANTS)
def test_missing_mathematics_is_outside_the_tolerance(seeded, variant, monkeypatch):
    params, tokens, _ = seeded
    config, model = CONFIG, model_of()
    if variant == "top_7_of_8":
        # A top-8-of-16 reference against a program that takes 7.
        config = dict(CONFIG, num_experts=16, num_experts_per_tok=8)
        model = model_of(n_experts=16, n_experts_per_tok=8)
        params = model.init(jax.random.key(4), tokens)
        program = model_of(n_experts=16, n_experts_per_tok=7)
    elif variant == "renormalised_weights":
        monkeypatch.setattr(moe, "route", _renormalised(moe.route))
        program = model
    elif variant == "no_qk_norm":
        program = model_of(qk_norm=False)
    elif variant == "capacity_drops_overflow":
        params = _skewed(params)
        monkeypatch.setattr(moe, "route", _with_capacity(moe.route))
        program = model
    elif variant == "up_adapter_from_gates_columns":
        monkeypatch.setattr(
            moe, "gate_and_up", _up_reads_gates_columns(moe.gate_and_up)
        )
        program = model
    else:
        params_without = jax.tree_util.tree_map_with_path(
            lambda path, v: jnp.zeros_like(v)
            if "mlp" in jax.tree_util.keystr(path)
            and "lora_b" in jax.tree_util.keystr(path) else v, params,
        )
        program = model
    want = plain.forward(config, params, tokens)
    if variant == "experts_without_adapters":
        got = program.apply(params_without, tokens)
    else:
        got = program.apply(params, tokens)
    assert relative(got, want) > MODEL_TOLERANCE
    monkeypatch.undo()
    # The same comparison with nothing missing is inside, by far.
    assert relative(model.apply(params, tokens), want) < 1e-4


def _layer_weights(key, rank=4):
    keys = jax.random.split(key, 9)
    shapes = [(D, F), (D, F), (F, D)]
    return [
        (jax.random.normal(keys[3 * i], (E,) + s) / s[0] ** 0.5,
         jax.random.normal(keys[3 * i + 1], (E, s[0], rank)) * 0.1,
         jax.random.normal(keys[3 * i + 2], (E, rank, s[1])) * 0.1)
        for i, s in enumerate(shapes)
    ]


def _dense_layer(x, weights, experts, layer):
    named = {
        name: dict(kernel=w[0], lora_a=w[1], lora_b=w[2])
        for name, w in zip(("w_gate", "w_up", "w_down"), layer)
    }
    return plain.dense_experts(
        x, named, plain.combine_of(weights, experts, E), 2.0
    )


@pytest.mark.parametrize("experts_of", [
    lambda n: jnp.full((n, 1), 3),  # every token to one expert, k = 1
    lambda n: jnp.tile(jnp.array([[3, 5]]), (n, 1)),  # two experts take all
    lambda n: jnp.stack([jnp.zeros(n, int), 1 + jnp.arange(n) % 7], 1),
], ids=["all_to_one", "all_to_two", "first_choice_all_to_one"])
def test_no_token_is_dropped_at_any_skew(experts_of):
    n = 48
    x = jax.random.normal(jax.random.key(0), (n, D))
    layer = _layer_weights(jax.random.key(1))
    experts = experts_of(n).astype(jnp.int32)
    weights = jax.random.uniform(jax.random.key(2), experts.shape) + 0.1
    got = moe.moe_ffn(x, (weights, experts), *layer, 2.0, jnp.float32)
    assert relative(got, _dense_layer(x, weights, experts, layer)) < 1e-5
    stats = moe.routing_stats(experts, E)
    assert int(stats["dropped"]) == 0
    assert int(stats["assignments"].sum()) == experts.size
    assert float(stats["max_over_mean"]) == pytest.approx(
        int(stats["assignments"].max()) * E / experts.size
    )


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation and its inverse, so that the gradient is
    a gather too (``g[inverse]``): how ``ops/moe.py`` moved whole arrays
    between token and expert order until PR 50, kept here for the layers the
    tests compare with."""
    return x[perm]


_permute_rows.defvjp(
    lambda x, perm, inverse: (x[perm], inverse),
    lambda inverse, grad: (grad[inverse], None, None),
)


def _per_projection_layer(x, routed, w_gate, w_up, w_down, lora_scale, dtype):
    """The layer as it was before the adapters shared their passes (PR 29):
    the tokens repeated k times and permuted, ``expert_projection`` for each
    of the three, the down adapter's output added in expert order."""
    weights, experts = routed
    n, k = experts.shape
    order, inverse, sizes = moe.dispatch_plan(experts, w_gate[0].shape[0])
    rows = _permute_rows(
        jnp.repeat(x.astype(dtype), k, axis=0), order, inverse
    )
    project = lambda v, w: moe.expert_projection(v, w, sizes, lora_scale, dtype)
    hidden = jax.nn.silu(project(rows, w_gate)) * project(rows, w_up)
    out = _permute_rows(project(hidden, w_down), inverse, order)
    return jnp.einsum(
        "nkd,nk->nd", out.reshape(n, k, -1), weights.astype(dtype)
    )


def _skewed_routing(key, n):
    """Two experts take most first choices and one expert nothing; weights
    as a softmax's top-k gives them (positive, not normalised)."""
    logits = jax.random.normal(key, (n, E)).at[:, :2].add(3.0)
    weights, experts = jax.lax.top_k(
        jax.nn.softmax(logits.at[:, 6].add(-50.0), -1), K
    )
    return weights, experts.astype(jnp.int32)


def _assert_trees_close(got, want, rtol=2e-5):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        scale = float(jnp.abs(w).max())
        assert scale > 0
        np.testing.assert_allclose(g, w, rtol=rtol, atol=rtol * scale)


def _assert_equal_over_peers(fn, reference, make_args, peers):
    """``fn`` against ``reference`` on ``make_args(i)`` (arrays only):
    unbatched for ``peers`` 0, else under ``vmap`` over that many stacked
    argument sets, each peer against the reference on its own."""
    if not peers:
        return _assert_trees_close(fn(*make_args(0)), reference(*make_args(0)))
    each = [make_args(i) for i in range(peers)]
    got = jax.vmap(fn)(*jax.tree.map(lambda *v: jnp.stack(v), *each))
    for i, args in enumerate(each):
        _assert_trees_close(jax.tree.map(lambda v: v[i], got), reference(*args))


PEERS = pytest.mark.parametrize("peers", [0, 2], ids=["unbatched", "vmap2"])
GROUP_SIZES = ([0, 7, 1, 0, 12, 20, 0, 0], [5, 5, 5, 5, 5, 5, 5, 5])


@PEERS
def test_merged_gate_and_up_equal_two_expert_projections(peers):
    """Values and the gradients to the rows, both ``lora_a``, both
    ``lora_b``."""
    n = 40

    def make_args(i):
        w_gate, w_up, _ = _layer_weights(jax.random.key(10 + i))
        keys = jax.random.split(jax.random.key(20 + i))
        return (jax.random.normal(keys[0], (n, D)), (w_gate[1:], w_up[1:]),
                (w_gate[0], w_up[0]), jnp.array(GROUP_SIZES[i], jnp.int32),
                jax.random.normal(keys[1], (2, n, F)))

    def graded(projections):
        def loss(rows, adapters, kernels, sizes, cots):
            w_gate, w_up = [(k,) + ab for k, ab in zip(kernels, adapters)]
            gate, up = projections(rows, w_gate, w_up, sizes, 2.0, jnp.float32)
            return jnp.sum(gate * cots[0]) + jnp.sum(up * cots[1]), (gate, up)

        return jax.value_and_grad(loss, (0, 1), has_aux=True)

    twice = lambda rows, w_gate, w_up, *rest: tuple(
        moe.expert_projection(rows, w, *rest) for w in (w_gate, w_up)
    )
    _assert_equal_over_peers(
        graded(moe.gate_and_up), graded(twice), make_args, peers
    )


@PEERS
def test_dispatch_from_the_tokens_equals_permuting_their_repeats(peers):
    n = 24

    def make_args(i):
        keys = jax.random.split(jax.random.key(30 + i))
        _, experts = _skewed_routing(keys[0], n)
        order, inverse, _ = moe.dispatch_plan(experts, E)
        return (jax.random.normal(keys[0], (n, D)), order, inverse,
                jax.random.normal(keys[1], (n * K, D)))

    def graded(dispatch):
        return jax.value_and_grad(
            lambda x, order, inverse, cot: (
                lambda rows: (jnp.sum(rows * cot), rows)
            )(dispatch(x, order, inverse)), has_aux=True,
        )

    _assert_equal_over_peers(
        graded(lambda x, o, i: moe._dispatch_rows(x, o, i, K)),
        graded(lambda x, o, i: _permute_rows(jnp.repeat(x, K, 0), o, i)),
        make_args, peers,
    )


@PEERS
def test_down_adapter_on_the_tokens_equals_the_expert_order_one(peers):
    """At a skewed routing: values and the gradients to ``hidden``,
    ``A_down``, ``B_down`` and the routing weights."""
    n = 24

    def make_args(i):
        keys = jax.random.split(jax.random.key(40 + i), 3)
        weights, experts = _skewed_routing(keys[0], n)
        _, lora_a, lora_b = _layer_weights(keys[1])[2]
        return (jax.random.normal(keys[2], (n * K, F)), lora_a, lora_b,
                weights, experts, jax.random.normal(keys[0], (n, D)))

    def graded(adapter):
        def loss(hidden, lora_a, lora_b, weights, experts, cot):
            order, inverse, sizes = moe.dispatch_plan(experts, E)
            down = moe.grouped_matmul(hidden, lora_a, sizes)
            y = adapter(down, lora_b, weights, experts, order, inverse, sizes)
            return jnp.sum(y * cot), y

        return jax.value_and_grad(loss, (0, 1, 2, 3), has_aux=True)

    def on_tokens(down, lora_b, weights, experts, order, inverse, sizes):
        down = _permute_rows(down, inverse, order).reshape(n, K, -1)
        return moe._down_adapter_on_tokens(
            down, (weights, experts), lora_b, 2.0, jnp.float32
        )

    def in_expert_order(down, lora_b, weights, experts, order, inverse, sizes):
        out = moe.grouped_matmul(down, lora_b, sizes) * 2.0
        per_choice = _permute_rows(out, inverse, order).reshape(n, K, -1)
        return jnp.einsum("nkd,nk->nd", per_choice, weights)

    _assert_equal_over_peers(
        graded(on_tokens), graded(in_expert_order), make_args, peers
    )


ADAPTED = {"all": (1, 1, 1), "gate_only": (1, 0, 1), "up_only": (0, 1, 1),
           "down_only": (0, 0, 1), "none": (0, 0, 0), "not_down": (1, 1, 0),
           "ranks_differ": (1, 2, 1)}


def _adapted_layer(key, which):
    """Layer weights with adapters on the projections ``which`` marks (2: at
    another rank than gate's)."""
    return [
        w if has == 1 else (w[0], None, None) if not has
        else _layer_weights(key, rank=6)[1]
        for w, has in zip(_layer_weights(key), ADAPTED[which])
    ]


@PEERS
@pytest.mark.parametrize("which", list(ADAPTED))
def test_layer_equals_the_per_projection_one(which, peers):
    """Whatever carries an adapter, at a skewed routing: values and the
    gradients to the tokens, every adapter and the routing weights."""
    n = 24

    def make_args(i):
        keys = jax.random.split(jax.random.key(50 + i), 3)
        weights, experts = _skewed_routing(keys[0], n)
        return (jax.random.normal(keys[1], (n, D)), weights,
                _adapted_layer(keys[2], which), experts,
                jax.random.normal(keys[0], (n, D)))

    def graded(layer):
        def loss(x, weights, w, experts, cot):
            y = layer(x, (weights, experts), *w, 2.0, jnp.float32)
            return jnp.sum(y * cot), y

        return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)

    _assert_equal_over_peers(
        graded(moe.moe_ffn), graded(_per_projection_layer), make_args, peers
    )


def _the_two_rules_apart(hidden, kernel, down, weights, order, inverse,
                         group_sizes, matmul, out_dtype):
    """``ops/moe._down_and_combine`` as two rules, the form before PR 50: the
    frozen down projection under the grouped matmul's own, then the experts'
    output gathered back to token order, that copy the einsum's residual;
    ``down`` by a permute of its own."""
    from dpwa_tpu.ops.wide import narrow

    n, k = weights.shape
    out = matmul(narrow(hidden, kernel.dtype), kernel, group_sizes)
    to_tokens = lambda v: _permute_rows(v, inverse, order).reshape(n, k, -1)
    y = jnp.einsum(
        "nkd,nk->nd", to_tokens(out), weights, preferred_element_type=out_dtype
    )
    return y, None if down is None else to_tokens(down)


@PEERS
@pytest.mark.parametrize("dtype, out_dtype", [
    (jnp.float32, None), (jnp.bfloat16, None), (jnp.bfloat16, jnp.float32),
], ids=["float32", "bfloat16", "bfloat16_wide"])
def test_combine_from_the_sorted_rows_equals_the_einsum_over_the_gathered_copy(
    peers, dtype, out_dtype
):
    """The down projection and the combine under their one rule, at a routing
    with two full groups and an empty one: ``y``, ``down`` in token order and
    the gradient to ``down`` to the bit; the gradients to ``hidden``, the
    kernel and the weights against the two rules apart in float32 (the one
    rule weights the product where the two weighted its operand, so a value
    is rounded at another place, as often)."""
    n, rank = 24, 4

    def make_args(i):
        keys = jax.random.split(jax.random.key(60 + i), 6)
        weights, experts = _skewed_routing(keys[0], n)
        order, inverse, sizes = moe.dispatch_plan(experts, E)
        assert int(sizes.min()) == 0 and int(sizes.max()) > 2 * n * K // E
        draw = lambda key, *shape: jax.random.normal(key, shape).astype(dtype)
        return (draw(keys[1], n * K, F), draw(keys[5], E, F, D) / F ** 0.5,
                draw(keys[2], n * K, rank), weights.astype(dtype), order,
                inverse, sizes,
                draw(keys[3], n, D).astype(out_dtype or dtype),
                draw(keys[4], n, K, rank))

    def graded(combine, out_dtype):
        def run(hidden, kernel, down, weights, order, inverse, sizes, *cots):
            results, pullback = jax.vjp(
                lambda hidden, kernel, down, weights: combine(
                    hidden, kernel, down, weights, order, inverse, sizes,
                    moe.grouped_matmul, out_dtype,
                ), hidden, kernel, down, weights,
            )
            # y, down, d_hidden, d_kernel, d_down, d_w
            return results + pullback(cots)

        return run

    def wide(*args):
        return graded(_the_two_rules_apart, None)(*(
            v.astype(jnp.float32) if v.dtype == dtype else v for v in args
        ))

    each = [make_args(i) for i in range(max(peers, 1))]
    over_peers = lambda fn: (
        jax.tree.map(lambda v: v[None], fn(*each[0])) if not peers
        else jax.vmap(fn)(*jax.tree.map(lambda *v: jnp.stack(v), *each))
    )
    got = over_peers(graded(moe._down_and_combine, out_dtype))
    was = over_peers(graded(_the_two_rules_apart, out_dtype))
    for g, w in zip(got, was):
        assert g.dtype == w.dtype and g.shape == w.shape
    for i in (0, 1, 4):
        np.testing.assert_array_equal(
            np.asarray(got[i], np.float32), np.asarray(was[i], np.float32)
        )
    # A sum made in float32 and rounded once; a product of two rounded values
    # rounded once more.
    room = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    for i in (2, 3, 5):
        want = over_peers(wide)[i]
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(
            np.asarray(got[i], np.float32), want, rtol=room, atol=room * scale
        )
        # No further from the wide values than the two rules apart were,
        # beyond the one rounding that moved.
        error = lambda v: float(jnp.abs(np.asarray(v, np.float32) - want).max())
        assert error(got[i]) <= error(was[i]) + room * scale


ONE_RULE_CASES = [
    # (a share, peers, under jax.checkpoint, an adapter on w_down, precision)
    *[(share, peers, checkpoint, True, "float32")
      for share in (False, True) for peers in (0, 2)
      for checkpoint in (False, True)],
    *[(share, 0, True, False, "float32") for share in (False, True)],
    *[(share, 2 * share, not share, adapted, precision)
      for precision in ("bfloat16", "bfloat16_wide")
      for adapted in (True, False) for share in (False, True)],
]
PRECISIONS = {"float32": (jnp.float32, None), "bfloat16": (jnp.bfloat16, None),
              "bfloat16_wide": (jnp.bfloat16, jnp.float32)}


@pytest.mark.parametrize(
    "share, peers, checkpoint, adapted, precision", ONE_RULE_CASES,
    ids=lambda v: {True: "yes", False: "no"}.get(v, str(v)),
)
def test_the_layer_under_one_rule_equals_the_layer_under_two(
    share, peers, checkpoint, adapted, precision, monkeypatch
):
    """``moe_ffn`` over all rows at once (every expert by ``grouped_matmul``;
    a share of four of the eight by ``held_matmul`` with no row cap), with the
    down projection and the combine under their one rule, against the same
    layer with the two rules apart: the result to the bit; the gradients to
    the tokens, the routing weights, every adapter leaf and the three frozen
    kernels (differentiated, as a dense-expert layer's are) within 1e-5 at
    float32, and at bfloat16 as far from the float32 layer's as the two
    rules' were."""
    dtype, out_dtype = PRECISIONS[precision]
    n, held = 24, E // 2 if share else E
    offset = 2 if share else None

    def make_args(i):
        keys = jax.random.split(jax.random.key(70 + i), 4)
        weights, experts = _skewed_routing(keys[0], n)
        w = _adapted_layer(keys[2], "all" if adapted else "not_down")
        if share:
            w = jax.tree.map(lambda v: v[offset:offset + held], w)
        w = jax.tree.map(lambda v: v.astype(dtype), w)
        return (jax.random.normal(keys[1], (n, D)).astype(out_dtype or dtype),
                weights, w, experts, jax.random.normal(keys[3], (n, D)))

    def graded(dtype, out_dtype):
        def layer(x, weights, w, experts):
            # (The tanh: a checkpoint's input is not the layer's own.)
            return moe.moe_ffn(
                jnp.tanh(x), (weights, experts), *w, 2.0, dtype, offset,
                out_dtype,
            )

        def loss(x, weights, w, experts, cot):
            fn = jax.checkpoint(layer) if checkpoint else layer
            y = fn(x, weights, w, experts)
            return jnp.sum(y.astype(jnp.float32) * cot), y

        grad = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)
        return jax.vmap(grad) if peers else grad

    each = [make_args(i) for i in range(max(peers, 1))]
    args = each[0] if not peers else jax.tree.map(
        lambda *v: jnp.stack(v), *each
    )
    in_float32 = jax.tree.map(
        lambda v: v.astype(jnp.float32) if v.dtype == dtype else v, args
    )
    (_, y), grads = graded(dtype, out_dtype)(*args)
    monkeypatch.setattr(moe, "_down_and_combine", _the_two_rules_apart)
    (_, y_was), grads_was = graded(dtype, out_dtype)(*args)
    _, grads_wide = graded(jnp.float32, None)(*in_float32)
    assert y.dtype == y_was.dtype == (out_dtype or dtype)
    np.testing.assert_array_equal(
        np.asarray(y, np.float32), np.asarray(y_was, np.float32)
    )
    leaves = jax.tree.leaves(grads)
    assert len(leaves) == 2 + 3 + 2 * (2 + adapted)
    for got, was, want in zip(
        leaves, jax.tree.leaves(grads_was), jax.tree.leaves(grads_wide)
    ):
        assert got.dtype == was.dtype and got.shape == want.shape
        scale = float(jnp.abs(want).max())
        assert scale > 0
        if dtype == jnp.float32:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * scale)
            continue
        # Against the float32 layer both carry the forward pass's roundings
        # (0.4 to 1.1 % of rms here); the rule moves one of the backward
        # pass's, and with ``out_dtype`` rounds ``d_y`` where it enters the
        # product, which the weights' gradient did not pass through before.
        assert relative(got, want) < 2.0 ** -6
        assert relative(got, want) < 1.5 * relative(was, want) + 2.0 ** -9


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_models_forward_pass_does_not_move_by_a_bit(seeded, dtype, monkeypatch):
    """The toy model's logits with the down projection and the combine under
    their one rule and under two: the same operations forward (the lowered
    text differs by where the down adapter's A side is traced, before the
    frozen product now)."""
    params, tokens, _ = seeded
    model = model_of(dtype)
    now = model.apply(params, tokens)
    monkeypatch.setattr(moe, "_down_and_combine", _the_two_rules_apart)
    np.testing.assert_array_equal(
        np.asarray(now, np.float32),
        np.asarray(model.apply(params, tokens), np.float32),
    )


def _wide_gathers(layer_grad, args, rows, width):
    """How many gathers whose result is ``[rows, width]`` the lowered
    program holds."""
    text = jax.jit(layer_grad).lower(*args).as_text()
    return len(re.findall(
        rf"stablehlo\.gather.*-> tensor<{rows}x{width}xf32>", text
    ))


@pytest.mark.parametrize("checkpoint, gathers, before", [
    (True, 5, 6), (False, 4, 4),
], ids=["checkpoint", "plain"])
def test_a_recomputed_layer_does_not_gather_its_output_again(
    checkpoint, gathers, before, monkeypatch
):
    """The wide row gathers of a layer's gradient: the dispatch, the output
    back to token order, the output's gradient from the tokens' rows, the
    rows' gradient back.  Under ``jax.checkpoint`` the forward pass runs a
    second time and the dispatch with it, but not the output's way back: the
    rule of the down projection and the combine keeps ``hidden`` (with the
    einsum over the gathered copy, ``before``, the residual was that copy,
    and was made again)."""
    n = 24
    weights, experts = _skewed_routing(jax.random.key(0), n)
    x = jax.random.normal(jax.random.key(1), (n, D))
    w = _layer_weights(jax.random.key(2))

    def count():
        # (A function of its own each time: a checkpoint's trace is kept by
        # the function it wraps.)
        def layer(x, weights, w):
            # The tanh stands for the norm before a block's feed-forward: the
            # checkpoint's input is not the layer's.
            return moe.moe_ffn(
                jnp.tanh(x), (weights, experts), *w, 2.0, jnp.float32
            )

        fn = jax.checkpoint(layer) if checkpoint else layer
        # Squared, so that the backward pass needs the layer's result and
        # the checkpoint has a forward pass to run before its second.
        loss = lambda *args: jnp.sum(fn(*args) ** 2)
        return _wide_gathers(jax.grad(loss, (0, 1, 2)), (x, weights, w), n * K, D)

    assert count() == gathers
    monkeypatch.setattr(moe, "_down_and_combine", _the_two_rules_apart)
    assert count() == before


def _grouped_products(layer, which):
    """(forward kind, weight kind): the grouped products left, after dead
    code is dropped, in the jaxpr of a layer's gradient to its adapters and
    to the tokens.  The forward kind is rows by their group's matrix (to the
    activations too, by its transpose); the weight kind contracts the rows
    of two operands group by group."""
    from jax.interpreters import partial_eval as pe

    n = 24
    weights, experts = _skewed_routing(jax.random.key(0), n)
    x = jax.random.normal(jax.random.key(1), (n, D))
    w = _adapted_layer(jax.random.key(2), which)
    kernels = [v[0] for v in w]

    def loss(x, adapters):
        w = [(k,) + tuple(ab) for k, ab in zip(kernels, adapters)]
        # Squared, so that the backward pass needs every forward product.
        return jnp.sum(layer(x, (weights, experts), *w, 2.0, jnp.float32) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, [v[1:] for v in w]).jaxpr
    text = str(pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))[0])
    assert text.count("ragged_dot_general") == (
        text.count("rhs_group_dimensions=(0,)")
        + text.count("rhs_group_dimensions=()")
    )
    return (text.count("rhs_group_dimensions=(0,)"),
            text.count("rhs_group_dimensions=()"))


@pytest.mark.parametrize("which, fewer", [
    ("all", (4, 2)),  # 2 and 1 by gate's and up's A sides, 2 and 1 by down's B
    ("not_down", (2, 1)),  # the A sides alone
    # Nothing to merge on the input side: the down adapter's B side alone.
    ("gate_only", (2, 1)), ("up_only", (2, 1)), ("down_only", (2, 1)),
    ("ranks_differ", (2, 1)),
    ("none", (0, 0)),
])
def test_the_adapters_share_their_grouped_products(which, fewer):
    """So that a later edit cannot silently split the pair again: against
    the per-projection layer, gate's and up's A sides cost two grouped
    products fewer of the forward kind (one forward, one to the activations)
    and one fewer of the weight kind; the down adapter's B side, applied
    after the combine, the same again."""
    was = _grouped_products(_per_projection_layer, which)
    now = _grouped_products(moe.moe_ffn, which)
    adapters = sum(1 for has in ADAPTED[which] if has)
    # 3 kernels forward and to the activations; an adapter is two products
    # forward, two to the activations and two to its factors.
    assert was == (6 + 4 * adapters, 2 * adapters)
    assert (was[0] - now[0], was[1] - now[1]) == fewer


def test_grouped_matmul_gradients_equal_the_dense_ones():
    m, k, n = 40, 16, 24
    lhs = jax.random.normal(jax.random.key(0), (m, k))
    rhs = jax.random.normal(jax.random.key(1), (E, k, n))
    sizes = jnp.array([0, 7, 1, 0, 12, 20, 0, 0], jnp.int32)
    group = jnp.repeat(jnp.arange(E), sizes, total_repeat_length=m)
    dense = lambda a, b: jnp.einsum("mk,mkn->mn", a, b[group])
    cot = jax.random.normal(jax.random.key(2), (m, n))
    got = jax.grad(
        lambda a, b: jnp.sum(moe.grouped_matmul(a, b, sizes) * cot), (0, 1)
    )(lhs, rhs)
    want = jax.grad(lambda a, b: jnp.sum(dense(a, b) * cot), (0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


def test_the_tpu_kernels_equal_ragged_dot(monkeypatch):
    """The path the chip takes (the library's Pallas kernels, interpreted
    here; steered in the test as the dispatcher asks the backend), against
    the path everything else takes: values and both gradients, an empty
    group among them, alone and with two peers folded into the groups."""
    from jax.experimental.pallas import tpu as pltpu

    m, k, n = 1024, 256, 16
    lhs = jax.random.normal(jax.random.key(0), (2, m, k))
    rhs = jax.random.normal(jax.random.key(1), (2, 4, k, n))
    sizes = jnp.array([[300, 0, 212, 512], [0, 1024, 0, 0]], jnp.int32)
    cot = jax.random.normal(jax.random.key(2), (m, n))
    one = jax.value_and_grad(
        lambda a, b, s: jnp.sum(moe.grouped_matmul(a, b, s) * cot), (0, 1)
    )
    want = jax.vmap(one)(lhs, rhs, sizes)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        assert "pallas_call" in str(jax.make_jaxpr(one)(lhs[0], rhs[0], sizes[0]))
        # At rest before the next dispatch: the TPU interpreter's callbacks
        # run JAX operations of their own (tests/test_lfm2.py has the story).
        alone = jax.block_until_ready(one(lhs[0], rhs[0], sizes[0]))
        folded = jax.block_until_ready(jax.vmap(one)(lhs, rhs, sizes))
    for got, ref in zip(jax.tree.leaves(folded), jax.tree.leaves(want)):
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-3)
    for got, ref in zip(jax.tree.leaves(alone), jax.tree.leaves(want)):
        np.testing.assert_allclose(got, ref[0], rtol=1e-4, atol=1e-3)


def test_vmap_over_two_peers_equals_a_loop_over_them(seeded):
    params, tokens, targets = seeded
    model = model_of()
    stacked = jax.tree.map(lambda v: jnp.stack([v, 1.02 * v]), params)
    batch = (jnp.stack([tokens, tokens[::-1]]),
             jnp.stack([targets, targets[::-1]]))
    grad_fn = jax.value_and_grad(lambda p, t, y: moe_loss(model, p, t, y))
    losses, grads = jax.jit(jax.vmap(grad_fn))(stacked, *batch)
    for i in range(2):
        loss, grad = grad_fn(
            jax.tree.map(lambda v: v[i], stacked), batch[0][i], batch[1][i]
        )
        assert float(losses[i]) == pytest.approx(float(loss), rel=1e-6)
        for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grad)):
            np.testing.assert_allclose(got[i], want, rtol=2e-4, atol=1e-6)


def test_the_peer_axis_folds_into_the_group_axis():
    """Under vmap the grouped matmul is one call with n x E groups."""
    lhs = jnp.ones((2, 16, 8))
    rhs = jnp.ones((2, E, 8, 4))
    sizes = jnp.array([[16] + [0] * 7, [0] * 7 + [16]], jnp.int32)
    jaxpr = str(jax.make_jaxpr(jax.vmap(moe.grouped_matmul))(lhs, rhs, sizes))
    assert jaxpr.count("ragged_dot_general") == 1
    assert f"i32[{2 * E}]" in jaxpr and "f32[32,8]" in jaxpr


def test_dense_path_is_what_it_was():
    """``n_experts == 0`` and no ``qk_norm``: the parameter tree of the dense
    decoder and a seeded toy loss, pinned at the parent commit (db9195b)."""
    cfg = LlamaConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq_len=32, lora_rank=4)
    model = Llama(cfg)
    tokens = jax.random.randint(jax.random.key(7), (2, 16), 0, 64)
    params = model.init(jax.random.key(0), tokens)
    names = {
        jax.tree_util.keystr(p)
        for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]
    }
    layer = {
        f"['params']['layer_0']['{block}']['{w}']['{leaf}']"
        for block, ws in (("attn", ("wq", "wk", "wv", "wo")),
                          ("mlp", ("w_gate", "w_up", "w_down")))
        for w in ws for leaf in ("kernel", "lora_a", "lora_b")
    } | {f"['params']['layer_0']['{n}']['scale']"
         for n in ("attn_norm", "mlp_norm")}
    assert {n for n in names if "layer_0" in n} == layer
    assert len(names) == 2 * len(layer) + 3
    params = jax.tree.map(
        lambda v: v + 0.05 * jnp.cos(
            jnp.arange(v.size, dtype=jnp.float32).reshape(v.shape)
        ), params,
    )
    loss = optax.softmax_cross_entropy_with_integer_labels(
        model.apply(params, tokens), jnp.roll(tokens, -1, 1)
    ).mean()
    assert float(loss) == pytest.approx(4.6457109451293945, rel=1e-6)


@pytest.mark.parametrize("transport", ["ici", "stacked"])
def test_one_step_of_the_toy_model_on_both_transports(transport):
    from dpwa_tpu.config import make_local_config
    from dpwa_tpu.train import init_params_per_peer
    from dpwa_tpu.utils.launch import build_transport

    n = 2
    model = model_of()
    bundle = build_transport(make_local_config(n, schedule="ring"), transport,
                             "native")
    stacked = init_params_per_peer(
        lambda key: model.init(key, jnp.zeros((1, 8), jnp.int32)),
        jax.random.key(0), n,
    )
    optimizer = lora_optimizer(
        optax.adam(1e-2), jax.tree.map(lambda v: v[0], stacked)
    )
    state = bundle.init_state(stacked, optimizer, bundle.transport)
    step = bundle.make_step(
        lambda p, batch: moe_loss(model, p, *batch), optimizer,
        bundle.transport, exchange_filter=lora_filter, overlap=False,
    )
    tokens = jax.random.randint(jax.random.key(1), (n, 2, T), 0, V)
    batch = (tokens, jnp.roll(tokens, -1, axis=-1))
    if bundle.batch_sharding is not None:
        batch = jax.device_put(batch, bundle.batch_sharding)
    before = jax.tree.map(np.asarray, adapters(state.params))
    state, losses, info = step(state, batch)
    assert np.all(np.isfinite(np.asarray(losses)))
    assert np.asarray(info.participated).all()
    after = adapters(state.params)
    moved = [n for n in before if not np.array_equal(before[n], after[n])]
    assert any("mlp" in n for n in moved) and any("attn" in n for n in moved)
