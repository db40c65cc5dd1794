"""Plain references: each family's forward pass in straightforward
``jax.numpy``, float32, highest matmul precision; no flax, no kernels, no
batching tricks.  ``forward(config, params, inputs) -> logits`` reads the
same parameter tree the program's model does, so seeded weights can be handed
to both."""
