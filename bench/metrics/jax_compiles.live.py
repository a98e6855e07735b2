"""``jax_compiles.steady`` in the live cell."""

import layers

read = layers.reader("jax_compiles.steady")
