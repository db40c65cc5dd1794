"""The traffic generator: a pure function of (seed, index)."""

import jax
import numpy as np
import pytest
from yardstick_paths import CELLS, cell_files

from benchmark import traffic

IMAGES = dict(kind="image_classes", pattern_size=4, noise=0.5)
TOKENS = dict(kind="markov_tokens", successors=4)
SHAPES = {
    "image_classes": dict(image_size=16, num_classes=10),
    "markov_tokens": dict(vocab_size=64, seq_len=32),
}


@pytest.mark.parametrize("task", [IMAGES, TOKENS], ids=lambda t: t["kind"])
def test_same_seed_same_batch_other_seed_other_batch(task):
    gen = traffic.make_generator(task, SHAPES[task["kind"]], 3, 2)
    a = gen(jax.random.key(7), 0)
    b = gen(jax.random.key(7), 0)
    c = gen(jax.random.key(8), 0)
    d = gen(jax.random.key(7), 1)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(d[0]))
    assert all(np.asarray(x).shape[:2] == (3, 2) for x in a)


def test_images_are_their_class_pattern_plus_noise():
    gen = traffic.make_generator(dict(IMAGES, noise=0.0), SHAPES["image_classes"], 2, 4)
    x, y = map(np.asarray, gen(jax.random.key(0), 0))
    assert x.shape == (2, 4, 16, 16, 3) and y.shape == (2, 4)
    same = [(i, j) for i in range(8) for j in range(i) if y.flat[i] == y.flat[j]]
    flat = x.reshape(8, -1)
    for i, j in same:
        assert np.array_equal(flat[i], flat[j])
    assert np.array_equal(x[0, 0, :4, :4], np.broadcast_to(x[0, 0, :1, :1], (4, 4, 3)))


def test_tokens_walk_the_chain_and_targets_are_shifted_inputs():
    gen = traffic.make_generator(TOKENS, SHAPES["markov_tokens"], 2, 3)
    tokens, targets = map(np.asarray, gen(jax.random.key(3), 0))
    assert tokens.shape == targets.shape == (2, 3, 32)
    assert np.array_equal(tokens[..., 1:], targets[..., :-1])
    more = [np.asarray(gen(jax.random.key(3), i)[0]) for i in range(1, 40)]
    follows = {}
    for t in [tokens] + more:
        for a, b in zip(t[..., :-1].ravel(), t[..., 1:].ravel()):
            follows.setdefault(int(a), set()).add(int(b))
    assert max(len(s) for s in follows.values()) <= 4


def test_unknown_kind_is_refused():
    with pytest.raises(ValueError):
        traffic.make_generator(dict(kind="nope"), {}, 1, 1)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_names_a_known_task(name):
    _, _, cell = cell_files(name)
    assert cell["task"]["kind"] in traffic.KINDS


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_generates_its_rehearsal_batch(name):
    import importlib

    _, config, cell = cell_files(name)
    builder = importlib.import_module("benchmark.builders." + config["family"])
    config, cell = builder.rehearse(config, cell)
    built = builder.build(config, cell)
    n, b = cell["peers"], cell["per_peer_batch"]
    batch = traffic.make_generator(cell["task"], built.batch_shape, n, b)(
        jax.random.key(0), 0
    )
    assert all(np.asarray(x).shape[:2] == (n, b) for x in batch)
    params = built.init_fn(jax.random.key(1))
    loss = built.loss_fn(params, jax.tree.map(lambda v: v[0], batch))
    assert np.isfinite(float(loss))
