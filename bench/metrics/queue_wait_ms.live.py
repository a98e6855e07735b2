"""``queue_wait_ms`` in the live cell."""

import layers

read = layers.reader("queue_wait_ms")
