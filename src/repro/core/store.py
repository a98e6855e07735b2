"""PagedStore: the paper's memory-mapping contribution, as a framework
primitive.

Two tiers are modelled:

1. **Host tier (faithful reproduction)** — the compressed index tensors
   (packed residual codes + centroid ids) live in files and are opened
   either fully-in-RAM (``mode="ram"``, np.fromfile — the ColBERTv2
   baseline) or memory-mapped (``mode="mmap"``, np.memmap — the paper's
   system). With mmap, the OS pages data in on access; we additionally
   track which 4 KiB pages each gather touches so tests can assert the
   multi-stage pipeline's access-minimisation claim directly.

2. **Device tier (TPU adaptation)** — ``DeviceBlockCache`` pins
   fixed-size token-blocks of the pool in device memory (HBM stand-in)
   with LRU eviction. Candidate gathers fetch only missing blocks. This
   is the HBM↔host analogue of page-cache behaviour and is shared by
   the recsys ``TieredEmbedding`` and the paged KV cache.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import threading
from collections import OrderedDict
from typing import Optional

import jax
import numpy as np

PAGE_BYTES = 4096


def rss_bytes() -> int:
    """Resident set size of this process (Linux)."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    return -1


@dataclasses.dataclass
class AccessStats:
    """Mmap access accounting. Mutation is thread-safe: the pipelined
    serving path updates it from dedicated gather-stage workers while
    benchmarks/health endpoints read it concurrently — all mutation
    goes through :meth:`account` under a lock, and readers that need a
    coherent view take :meth:`snapshot`. (Bare field reads remain fine
    for single-threaded tests.)"""

    gathers: int = 0
    tokens_read: int = 0
    pages_touched: int = 0            # residual pages, cumulative
    unique_pages: Optional[set] = None  # residual pages, deduplicated
    residual_gathers: int = 0         # gathers that faulted residual rows
    residual_tokens_read: int = 0     # rows read from the residual file
    h2d_bytes: int = 0                # gathered bytes bound for the device

    def __post_init__(self):
        self._lock = threading.Lock()

    def reset(self):
        with self._lock:
            self.gathers = 0
            self.tokens_read = 0
            self.pages_touched = 0
            self.unique_pages = set()
            self.residual_gathers = 0
            self.residual_tokens_read = 0
            self.h2d_bytes = 0

    def account(self, token_ids: np.ndarray, packed_dim: int,
                residuals: bool = True):
        """Record one gather of ``token_ids`` rows (atomically)."""
        n = int(token_ids.size)
        if residuals:
            # which 4 KiB pages of residuals.bin do these rows touch?
            byte_lo = token_ids.astype(np.int64) * packed_dim
            pages = np.unique(byte_lo // PAGE_BYTES)
        with self._lock:
            self.gathers += 1
            self.tokens_read += n
            if not residuals:
                return
            self.residual_gathers += 1
            self.residual_tokens_read += n
            self.pages_touched += len(pages)
            if self.unique_pages is not None:
                self.unique_pages.update(pages.tolist())

    def transfer(self, *arrays):
        """Record host arrays a gather hands on for the host→device copy
        (their ``nbytes``, padded rows included)."""
        n = sum(a.nbytes for a in arrays)
        with self._lock:
            self.h2d_bytes += n

    def snapshot(self) -> dict:
        """Atomic, plain-dict copy for cross-thread readers (per-stage
        instrumentation deltas, tests, benchmarks)."""
        with self._lock:
            return {"gathers": self.gathers,
                    "tokens_read": self.tokens_read,
                    "pages_touched": self.pages_touched,
                    "unique_pages": len(self.unique_pages or ()),
                    "residual_gathers": self.residual_gathers,
                    "residual_tokens_read": self.residual_tokens_read,
                    "h2d_bytes": self.h2d_bytes}


class PagedStore:
    """Column store of per-token index payloads with ram/mmap modes."""

    def __init__(self, path, mode: str = "mmap"):
        self.path = pathlib.Path(path)
        self.mode = mode
        meta = json.loads((self.path / "meta.json").read_text())
        self.n_tokens = meta["n_tokens"]
        self.packed_dim = meta["packed_dim"]
        self.nbits = meta["nbits"]
        self.dim = meta["dim"]

        rbytes = self.n_tokens * self.packed_dim
        if mode == "mmap":
            self.residuals = np.memmap(self.path / "residuals.bin", np.uint8,
                                       "r", shape=(self.n_tokens, self.packed_dim))
            self.codes = np.memmap(self.path / "codes.bin", np.int32, "r",
                                   shape=(self.n_tokens,))
        elif mode == "ram":
            self.residuals = np.fromfile(self.path / "residuals.bin",
                                         np.uint8).reshape(self.n_tokens,
                                                           self.packed_dim)
            self.codes = np.fromfile(self.path / "codes.bin", np.int32)
        else:
            raise ValueError(mode)
        assert self.residuals.size == rbytes
        self.stats = AccessStats()
        self.stats.reset()

    # -- access ---------------------------------------------------------
    def gather_tokens(self, token_ids: np.ndarray):
        """token_ids: (N,) int64 → (codes (N,), residuals (N, packed))."""
        token_ids = np.asarray(token_ids)
        res = self.residuals[token_ids]
        cds = self.codes[token_ids]
        self._account(token_ids)
        return cds, res

    def gather_ranges(self, starts: np.ndarray, length: int):
        """Uniform-stride gather: rows [s, s+length) per start (clamped)."""
        flat = self._range_ids(starts, length)
        res = self.residuals[flat].reshape(len(starts), length, self.packed_dim)
        cds = self.codes[flat].reshape(len(starts), length)
        self._account(flat)
        return cds, res

    def gather_codes_ranges(self, starts: np.ndarray, length: int):
        """Codes-only uniform-stride gather for the approximate stage:
        reads centroid ids and *never touches a residual page* — the
        access pattern the paper's stage 3 relies on in mmap mode."""
        flat = self._range_ids(starts, length)
        cds = self.codes[flat].reshape(len(starts), length)
        self._account(flat, residuals=False)
        return cds

    def _range_ids(self, starts: np.ndarray, length: int):
        idx = starts[:, None] + np.arange(length)[None, :]
        idx = np.minimum(idx, self.n_tokens - 1)
        return idx.reshape(-1)

    def _account(self, token_ids, residuals: bool = True):
        self.stats.account(token_ids, self.packed_dim, residuals=residuals)

    # -- info -------------------------------------------------------------
    def total_bytes(self) -> int:
        return self.n_tokens * (self.packed_dim + 4)

    def resident_fraction_estimate(self) -> float:
        """Fraction of the pool's pages ever touched (mmap working set)."""
        total_pages = max(1, self.total_bytes() // PAGE_BYTES)
        return len(self.stats.unique_pages or ()) / total_pages

    # -- construction ------------------------------------------------------
    @staticmethod
    def write(path, codes: np.ndarray, residuals: np.ndarray, *, dim: int,
              nbits: int):
        path = pathlib.Path(path)
        path.mkdir(parents=True, exist_ok=True)
        n_tokens, packed_dim = residuals.shape
        residuals.astype(np.uint8).tofile(path / "residuals.bin")
        codes.astype(np.int32).tofile(path / "codes.bin")
        (path / "meta.json").write_text(json.dumps({
            "n_tokens": int(n_tokens), "packed_dim": int(packed_dim),
            "dim": dim, "nbits": nbits}))


class DeviceBlockCache:
    """LRU block cache: host pool → device arrays (the HBM tier).

    The pool is split into blocks of ``block_tokens`` rows. ``lookup``
    returns device arrays for the requested blocks, fetching misses via
    ``jax.device_put`` and evicting least-recently-used blocks beyond
    ``capacity_blocks``. Miss/hit counters feed the latency model and
    benchmarks.
    """

    def __init__(self, store: PagedStore, block_tokens: int = 4096,
                 capacity_blocks: int = 64):
        self.store = store
        self.block_tokens = block_tokens
        self.capacity = capacity_blocks
        self._cache: OrderedDict[int, tuple] = OrderedDict()
        self.hits = 0
        self.misses = 0

    def n_blocks(self) -> int:
        return -(-self.store.n_tokens // self.block_tokens)

    def _fetch(self, block_id: int):
        lo = block_id * self.block_tokens
        hi = min(lo + self.block_tokens, self.store.n_tokens)
        idx = np.arange(lo, hi)
        cds, res = self.store.gather_tokens(idx)
        pad = self.block_tokens - (hi - lo)
        if pad:
            cds = np.pad(cds, (0, pad))
            res = np.pad(res, ((0, pad), (0, 0)))
        return (jax.device_put(cds), jax.device_put(res))

    def lookup(self, block_ids):
        out = {}
        for b in dict.fromkeys(int(b) for b in block_ids):
            if b in self._cache:
                self._cache.move_to_end(b)
                self.hits += 1
            else:
                self.misses += 1
                self._cache[b] = self._fetch(b)
                while len(self._cache) > self.capacity:
                    self._cache.popitem(last=False)
            out[b] = self._cache[b]
        return out

    def gather_rows(self, token_ids: np.ndarray):
        """Gather rows through the block cache (device-side assembly)."""
        import jax.numpy as jnp
        token_ids = np.asarray(token_ids)
        blocks = token_ids // self.block_tokens
        cache = self.lookup(np.unique(blocks))
        cds = np.zeros(token_ids.shape, np.int32)
        res = np.zeros((*token_ids.shape, self.store.packed_dim), np.uint8)
        flat_ids = token_ids.reshape(-1)
        flat_blocks = flat_ids // self.block_tokens
        # assemble per-block (host copy of device block slices)
        cds_f = cds.reshape(-1)
        res_f = res.reshape(-1, self.store.packed_dim)
        for b in np.unique(flat_blocks):
            sel = flat_blocks == b
            off = flat_ids[sel] - b * self.block_tokens
            bc, br = cache[int(b)]
            cds_f[sel] = np.asarray(jnp.take(bc, off, axis=0))
            res_f[sel] = np.asarray(jnp.take(br, off, axis=0))
        return cds_f.reshape(token_ids.shape), \
            res_f.reshape(*token_ids.shape, self.store.packed_dim)
