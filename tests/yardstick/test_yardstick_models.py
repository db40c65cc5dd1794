"""The program's models against the configurations' plain references, at toy
sizes on the CPU (on the chip ``run.py`` repeats this at published widths)."""

import importlib

import jax
import jax.numpy as jnp
import pytest
from yardstick_paths import CONFIGS, MANIFEST, cell_files

from benchmark import reference, traffic


def _toy(config_name, compute_dtype="float32"):
    cell_name = next(
        w["name"] for w in MANIFEST["workloads"] if w["config"] == config_name
    )
    _, config, cell = cell_files(cell_name)
    builder = importlib.import_module("benchmark.builders." + config["family"])
    config, cell = builder.rehearse(config, cell)
    config["assumed"]["compute_dtype"] = compute_dtype
    built = builder.build(config, dict(cell, peers=2))
    params = jax.vmap(built.init_fn)(jax.random.split(jax.random.key(0), 2))
    # Zero-initialised leaves (LoRA B, biases) must matter to the comparison.
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    params = treedef.unflatten([
        v + 0.05 * jax.random.normal(k, v.shape, v.dtype)
        for v, k in zip(leaves, keys)
    ])
    batch = traffic.make_generator(
        cell["task"], built.batch_shape, 2, cell["per_peer_batch"]
    )(jax.random.key(2), 0)
    return built, params, batch


def _relative_error(built, params, batch, apply_fn=None):
    error, size = reference.make_model_check(
        apply_fn or built.apply_fn, built.reference_forward,
        built.reference_inputs,
    )(params, batch)
    return float(error) / float(size)


@pytest.mark.parametrize("name", CONFIGS)
def test_model_equals_its_plain_reference_in_float32(name):
    assert _relative_error(*_toy(name)) < 1e-4


@pytest.mark.parametrize("name", CONFIGS)
def test_bfloat16_compute_is_inside_the_tolerance(name):
    error = _relative_error(*_toy(name, "bfloat16"))
    assert 1e-5 < error < reference.MODEL_TOLERANCE


@pytest.mark.parametrize("name", CONFIGS)
def test_a_coarser_type_or_missing_mathematics_is_outside(name):
    built, params, batch = _toy(name)

    def coarse(p, x):  # weights through an 8-bit float
        return built.apply_fn(jax.tree.map(
            lambda v: v.astype(jnp.float8_e4m3fn).astype(v.dtype), p
        ), x)

    def without_adapters(p, x):
        return built.apply_fn(jax.tree_util.tree_map_with_path(
            lambda path, v: jnp.zeros_like(v)
            if "lora_b" in jax.tree_util.keystr(path) else v, p
        ), x)

    assert _relative_error(built, params, batch, coarse) > reference.MODEL_TOLERANCE
    if built.exchange_filter is not None:
        assert _relative_error(
            built, params, batch, without_adapters
        ) > reference.MODEL_TOLERANCE
