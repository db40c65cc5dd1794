"""The one traffic generator: a cell's ``task`` parameters -> peer-stacked
batches made on the device from the seed.

A cell file names a task ``kind`` and its parameters; nothing here knows a
cell or a model by name.  Both tasks are learnable, so a loss after K steps
means something, and both are a pure function of (seed, batch index): the
same seed gives the same batches.

- ``image_classes``: ``classes`` seeded low-resolution patterns
  (``pattern_size``^2 x 3), upsampled to the model's image size, plus
  ``noise`` x unit normal noise; the label is the class.
- ``markov_tokens``: a seeded first-order Markov chain over the vocabulary in
  which every token has ``successors`` equally likely successors; its entropy
  rate, ln(successors), is the floor of the next-token loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _image_classes(task, shape, n, b):
    size, classes = shape["image_size"], shape["num_classes"]
    r = task["pattern_size"]
    if size % r:
        raise ValueError(f"pattern_size {r} does not divide image {size}")

    def make(key, index):
        patterns = jax.random.normal(
            jax.random.fold_in(key, 0), (classes, r, r, 3), jnp.float32
        )
        k_label, k_noise = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(key, 1), index)
        )
        labels = jax.random.randint(k_label, (n, b), 0, classes, jnp.int32)
        x = patterns[labels]
        x = jnp.repeat(jnp.repeat(x, size // r, axis=2), size // r, axis=3)
        x = x + task["noise"] * jax.random.normal(k_noise, x.shape, x.dtype)
        return x, labels

    return make


def _markov_tokens(task, shape, n, b):
    vocab, t = shape["vocab_size"], shape["seq_len"]
    s = task["successors"]

    def make(key, index):
        table = jax.random.randint(
            jax.random.fold_in(key, 0), (vocab, s), 0, vocab, jnp.int32
        )
        k_first, k_choice = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(key, 1), index)
        )
        first = jax.random.randint(k_first, (n, b), 0, vocab, jnp.int32)
        choices = jax.random.randint(k_choice, (t, n, b), 0, s, jnp.int32)

        def walk(token, choice):
            nxt = table[token, choice]
            return nxt, nxt

        _, rest = jax.lax.scan(walk, first, choices)
        tokens = jnp.concatenate([first[None], rest], 0)  # [t + 1, n, b]
        tokens = jnp.moveaxis(tokens, 0, -1)
        return tokens[..., :-1], tokens[..., 1:]

    return make


KINDS = {"image_classes": _image_classes, "markov_tokens": _markov_tokens}


def make_generator(task: dict, shape: dict, n_peers: int, per_peer_batch: int,
                   sharding=None):
    """A jitted ``(key, index) -> batch``: the batch is peer-stacked
    (``[n_peers, per_peer_batch, ...]``), lives on the device, and lands in
    ``sharding`` where the transport wants one."""
    if task["kind"] not in KINDS:
        raise ValueError(
            f"unknown task kind {task['kind']!r}; known: {sorted(KINDS)}"
        )
    make = KINDS[task["kind"]](task, shape, n_peers, per_peer_batch)
    return jax.jit(make, out_shardings=sharding)
