"""End-to-end arithmetic over the load generator's record.

Times are seconds from the window's start, ``t0``. A request's latency
runs from its due time to its parsed reply, so a late send or a stall
counts against every request it delays. A request that failed or got no
reply counts as infinitely late: it misses every latency limit.
"""

from __future__ import annotations

import numpy as np

OK = 0


def latencies_ms(client: dict) -> np.ndarray:
    """Latency of every request due in the window, in ms (inf where it
    failed or got no reply)."""
    lat = (client["done"] - client["due"]) * 1e3
    return np.where(client["status"] == OK, lat, np.inf)


def percentile_ms(client: dict, q: float) -> float:
    """The q-th percentile of all requests' latencies (NumPy's linear
    interpolation between the closest ranks)."""
    return float(np.percentile(latencies_ms(client), q))


def qps(client: dict, seconds: float) -> float:
    """Answered requests whose reply came inside the window, per second
    of window. A request still queued at the close is not counted: the
    window does not drain the backlog."""
    inside = (client["status"] == OK) & (client["done"] <= seconds)
    return float(np.sum(inside)) / seconds


def lateness_ms(client: dict) -> np.ndarray:
    """How late the generator sent each request: send minus due time."""
    return (client["sent"] - client["due"]) * 1e3


def failed(client: dict) -> int:
    """Requests that failed or got no reply, writes among them."""
    return int(np.sum(client["status"] != OK)
               + np.sum(client.get("w_status", np.zeros(0)) != OK))


def writes_acked(client: dict) -> int:
    return int(np.sum(client.get("w_status", np.zeros(0)) == OK))


def in_flight(client: dict, seconds: float) -> int:
    """Queries sent by the window's close whose reply came after it, or
    never."""
    sent = client["sent"] <= seconds
    return int(np.sum(sent & ~(client["done"] <= seconds)))


def sample(client: dict, n: int, seed: int) -> np.ndarray:
    """Up to ``n`` answered requests, drawn from the seed, for the
    comparison with the reference; sorted."""
    done = np.flatnonzero(client["status"] == OK)
    rng = np.random.default_rng([seed % (1 << 64), 0xC4EC])
    return np.sort(rng.choice(done, min(n, len(done)), replace=False))
