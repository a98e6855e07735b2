"""``idle_waiting.steady`` in the live cell."""

import layers

read = layers.reader("idle_waiting.steady")
