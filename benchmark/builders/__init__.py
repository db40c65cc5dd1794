"""One module per model family, found by a configuration file's ``family``.

A builder module offers ``rehearse(config, cell) -> (config, cell)`` (the toy
shapes of a CPU rehearsal) and ``build(config, cell) -> Built``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class Built:
    """What the harness needs of a configuration under a cell."""

    init_fn: Callable  # key -> one peer's parameters
    loss_fn: Callable  # (one peer's parameters, one peer's batch) -> scalar
    make_optimizer: Callable  # one peer's parameter shapes -> optax transform
    exchange_filter: Optional[Callable[[str], bool]]  # None: the whole tree
    batch_shape: dict  # what traffic.make_generator needs of the model
    flops_per_sample: float  # forward + backward, required, per sample
    apply_fn: Callable  # (one peer's parameters, inputs) -> logits
    reference_forward: Callable  # the same by benchmark/references/<family>
    reference_inputs: Callable  # one peer's batch -> the sample both are given
    kernel_work: Any = None  # per-step {"flops", "bytes"} of named kernels


def make_optax(spec: dict):
    """An optax transform from a configuration's (or a cell's) ``optimizer``
    group: ``{"name": "sgd" | "adam" | "adamw", "learning_rate": ...}``."""
    import optax

    spec = dict(spec)
    name, lr = spec.pop("name"), spec.pop("learning_rate")
    makers = {"sgd": optax.sgd, "adam": optax.adam, "adamw": optax.adamw}
    if name not in makers:
        raise ValueError(f"unknown optimizer {name!r}; known: {sorted(makers)}")
    return makers[name](lr, **spec)
