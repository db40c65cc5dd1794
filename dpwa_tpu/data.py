"""Offline datasets and per-peer data streams.

Gossip training's defining trait: **each peer trains on its own data
stream** (SURVEY.md "What dpwa is").  :func:`peer_batches` materializes that —
given one dataset it deals every peer a disjoint shard and an independent
shuffle, and yields peer-stacked ``[n_peers, batch, ...]`` arrays ready to be
sharded over the mesh.

This box has zero network egress, so the loaders are offline-first:
``sklearn``'s bundled 8×8 digits for a real image-classification task, plus
synthetic Gaussian-blob tasks for fast unit tests.  A real MNIST/CIFAR
directory is picked up if one exists on disk."""

from __future__ import annotations

import os
from typing import Iterator, Tuple

import numpy as np

Array = np.ndarray


def gaussian_blobs(
    n_classes: int = 4,
    dim: int = 16,
    n_per_class: int = 256,
    seed: int = 0,
    spread: float = 0.5,
) -> Tuple[Array, Array]:
    """Linearly separable-ish classification task for fast tests."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_classes, dim)) * 3.0
    xs, ys = [], []
    for c in range(n_classes):
        xs.append(centers[c] + spread * rng.standard_normal((n_per_class, dim)))
        ys.append(np.full(n_per_class, c))
    x = np.concatenate(xs).astype(np.float32)
    y = np.concatenate(ys).astype(np.int32)
    order = rng.permutation(len(x))
    return x[order], y[order]


def load_digits_dataset(
    test_fraction: float = 0.2, seed: int = 0
) -> Tuple[Array, Array, Array, Array]:
    """8×8 grayscale digits (1797 samples, bundled with sklearn) as NHWC."""
    from sklearn.datasets import load_digits

    digits = load_digits()
    x = (digits.images.astype(np.float32) / 16.0)[..., None]  # [N, 8, 8, 1]
    y = digits.target.astype(np.int32)
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(x))
    x, y = x[order], y[order]
    n_test = int(len(x) * test_fraction)
    return x[n_test:], y[n_test:], x[:n_test], y[:n_test]


def find_mnist_dir() -> str | None:
    """Look for an on-disk MNIST (idx or npz) without any network access."""
    for root in ("/root/datasets", "/root/data", "/datasets", "/tmp/mnist"):
        if os.path.isdir(root):
            for name in ("mnist.npz", "train-images-idx3-ubyte"):
                if os.path.exists(os.path.join(root, name)):
                    return root
    return None


def load_mnist_or_digits() -> Tuple[Array, Array, Array, Array, str]:
    """Full MNIST if present on disk, else the bundled 8×8 digits.

    Returns (x_train, y_train, x_test, y_test, dataset_name)."""
    root = find_mnist_dir()
    if root is not None:
        npz = os.path.join(root, "mnist.npz")
        if os.path.exists(npz):
            with np.load(npz) as d:
                x_tr = d["x_train"].astype(np.float32)[..., None] / 255.0
                x_te = d["x_test"].astype(np.float32)[..., None] / 255.0
                return (
                    x_tr,
                    d["y_train"].astype(np.int32),
                    x_te,
                    d["y_test"].astype(np.int32),
                    "mnist",
                )
    x_tr, y_tr, x_te, y_te = load_digits_dataset()
    return x_tr, y_tr, x_te, y_te, "digits"


def peer_split(
    x: Array, y: Array, n_peers: int, seed: int = 0
) -> Tuple[list, list]:
    """Deal the dataset into n disjoint per-peer shards (own data streams)."""
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(x))
    shard = len(x) // n_peers
    xs = [x[order[i * shard : (i + 1) * shard]] for i in range(n_peers)]
    ys = [y[order[i * shard : (i + 1) * shard]] for i in range(n_peers)]
    return xs, ys


class PeerBatchStream:
    """Endless stream of peer-stacked batches ``([n, b, ...], [n, b])``.

    Each peer cycles its own shard with an independent shuffle — the
    SPMD stand-in for the reference's N independent data loaders.

    The stream is **checkpointable**: :meth:`state_dict` captures every
    peer's RNG state and epoch cursor (JSON-serializable), and
    :meth:`load_state_dict` restores them, so a resumed run reproduces
    the original batch sequence exactly — the data-side counterpart of
    saving the gossip schedule position (``GossipTrainState.step``).
    The dataset itself is not saved; reconstruct the stream with the
    same ``(x, y, n_peers, batch_size, seed)`` before restoring."""

    def __init__(
        self,
        x: Array,
        y: Array,
        n_peers: int,
        batch_size: int,
        seed: int = 0,
    ):
        self.n_peers = n_peers
        self.batch_size = batch_size
        self.xs, self.ys = peer_split(x, y, n_peers, seed)
        self._rngs = [
            np.random.default_rng(seed + 1000 + i) for i in range(n_peers)
        ]
        self._cursors = [np.array([], dtype=np.int64)] * n_peers
        self.batch_count = 0

    def __iter__(self) -> "PeerBatchStream":
        return self

    def __next__(self) -> Tuple[Array, Array]:
        bx, by = [], []
        for i in range(self.n_peers):
            while len(self._cursors[i]) < self.batch_size:
                self._cursors[i] = np.concatenate(
                    [self._cursors[i], self._rngs[i].permutation(len(self.xs[i]))]
                )
            take, self._cursors[i] = (
                self._cursors[i][: self.batch_size],
                self._cursors[i][self.batch_size :],
            )
            bx.append(self.xs[i][take])
            by.append(self.ys[i][take])
        self.batch_count += 1
        return np.stack(bx), np.stack(by)

    def state_dict(self) -> dict:
        """JSON-serializable snapshot of the stream position."""
        return {
            "n_peers": self.n_peers,
            "batch_size": self.batch_size,
            "batch_count": self.batch_count,
            "cursors": [c.tolist() for c in self._cursors],
            # PCG64 state is a pair of (arbitrary-precision) ints plus two
            # small fields — all JSON-safe in Python.
            "rng_states": [r.bit_generator.state for r in self._rngs],
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore a :meth:`state_dict` snapshot.

        Raises on any stream-parameter mismatch: restoring into a stream
        built with a different peer count or batch size would replay a
        DIFFERENT sequence than the original run — the silent divergence
        this whole mechanism exists to prevent."""
        for field, mine in (
            ("n_peers", self.n_peers),
            ("batch_size", self.batch_size),
        ):
            # Older snapshots (no recorded batch_size) skip that check.
            if field in state and int(state[field]) != mine:
                raise ValueError(
                    f"stream state was saved with {field}="
                    f"{int(state[field])}, this stream has {field}={mine}"
                )
        if (
            len(state["cursors"]) != self.n_peers
            or len(state["rng_states"]) != self.n_peers
        ):
            raise ValueError(
                f"stream state covers {len(state['cursors'])} peers "
                f"({len(state['rng_states'])} rng states), this stream "
                f"has {self.n_peers}"
            )
        self.batch_count = int(state["batch_count"])
        self._cursors = [
            np.asarray(c, dtype=np.int64) for c in state["cursors"]
        ]
        for r, s in zip(self._rngs, state["rng_states"]):
            r.bit_generator.state = s


def peer_batches(
    x: Array,
    y: Array,
    n_peers: int,
    batch_size: int,
    seed: int = 0,
) -> PeerBatchStream:
    """Build a :class:`PeerBatchStream` (kept as the historical
    functional entry point; the returned object is a plain iterator that
    additionally supports ``state_dict``/``load_state_dict``)."""
    return PeerBatchStream(x, y, n_peers, batch_size, seed)


def device_prefetch(
    batches: Iterator, size: int = 2, sharding=None
) -> Iterator:
    """Stage host batches onto the device ahead of use.

    ``jax.device_put`` is async: keeping ``size`` batches in flight lets
    the host→device copy of batch k+1 overlap the training step on batch
    k instead of serializing in the jit call's implicit transfer.  On a
    host with a slow device link this is the difference between
    transfer-bound and compute-bound stepping; elsewhere it still hides
    the copy latency.

    ``sharding`` (e.g. :func:`dpwa_tpu.parallel.mesh.peer_sharding`)
    places each batch directly in its mesh layout.
    """
    import collections

    import jax

    put = (
        (lambda b: jax.device_put(b, sharding))
        if sharding is not None
        else jax.device_put
    )
    buf = collections.deque()
    for item in batches:
        buf.append(put(item))
        if len(buf) >= max(1, size):
            yield buf.popleft()
    while buf:
        yield buf.popleft()
