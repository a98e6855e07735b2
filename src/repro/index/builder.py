"""End-to-end ColBERTv2 index construction.

embeddings (n_docs, doc_maxlen, dim) + lengths
    → k-means centroids → residual codec → packed codes/residuals
    → IVF → on-disk index directory (PagedStore format + metadata).
"""

from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.store import PagedStore
from repro.index import ivf as ivf_mod
from repro.index import kmeans, residual


_ENCODE_SLAB = 1 << 20        # tokens per residual-encoding dispatch


def build_colbert_index(out_dir, doc_embs: np.ndarray, doc_lens: np.ndarray,
                        *, nbits: int = 4, n_centroids: int | None = None,
                        kmeans_iters: int = 8, sample_cap: int = 65536,
                        seed: int = 0, centroids: np.ndarray | None = None,
                        bucket_cutoffs: np.ndarray | None = None,
                        bucket_weights: np.ndarray | None = None):
    """doc_embs: (n_docs, doc_maxlen, dim) unit-norm; doc_lens: (n_docs,).

    Passing ``centroids`` + ``bucket_cutoffs`` + ``bucket_weights`` pins
    the geometry: k-means training and codec fitting are skipped and the
    corpus is encoded against the given codec. The live-index rebuild
    oracle uses this so a from-scratch rebuild of a mutated corpus is
    bitwise comparable to serving the base index + delta segment (both
    sides then quantise residuals identically)."""
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    n_docs, doc_maxlen, dim = doc_embs.shape

    # flatten valid tokens
    valid = np.arange(doc_maxlen)[None, :] < doc_lens[:, None]
    flat = doc_embs[valid]                                   # (n_tokens, dim)
    token_pids = np.repeat(np.arange(n_docs), doc_lens)
    n_tokens = flat.shape[0]

    if centroids is not None:
        if bucket_cutoffs is None or bucket_weights is None:
            raise ValueError("pinned geometry needs centroids, "
                             "bucket_cutoffs and bucket_weights together")
        centroids = np.asarray(centroids, np.float32)
        n_centroids = int(centroids.shape[0])
        codec = residual.ResidualCodec(
            centroids=jnp.asarray(centroids),
            bucket_cutoffs=jnp.asarray(bucket_cutoffs, jnp.float32),
            bucket_weights=jnp.asarray(bucket_weights, jnp.float32),
            nbits=nbits)
        cids = np.asarray(kmeans.assign(jnp.asarray(flat),
                                        jnp.asarray(centroids))[0])
    else:
        if n_centroids is None:
            n_centroids = max(16, min(kmeans.pick_n_centroids(n_tokens),
                                      n_tokens // 4))

        rng = np.random.default_rng(seed)
        sample = flat[rng.choice(n_tokens, min(sample_cap, n_tokens),
                                 replace=False)]
        centroids = kmeans.train_kmeans(jax.random.PRNGKey(seed),
                                        jnp.asarray(sample), n_centroids,
                                        kmeans_iters)
        centroids = np.asarray(centroids, np.float32)

        cids, _ = kmeans.assign(jnp.asarray(flat), jnp.asarray(centroids))
        cids = np.asarray(cids)

        codec = residual.fit_codec(centroids, sample,
                                   np.asarray(kmeans.assign(
                                       jnp.asarray(sample),
                                       jnp.asarray(centroids))[0]), nbits)
    # encode in slabs: one call over millions of tokens needs more
    # device memory than a chip has (the per-token bucket search keeps
    # several (N, dim) temporaries); rows are independent, so slabs
    # produce the same codes
    packed = np.concatenate([np.asarray(residual.encode_residuals(
        jnp.asarray(flat[lo:lo + _ENCODE_SLAB]),
        jnp.asarray(cids[lo:lo + _ENCODE_SLAB]), codec.centroids,
        codec.bucket_cutoffs, nbits))
        for lo in range(0, max(n_tokens, 1), _ENCODE_SLAB)])

    # persist
    PagedStore.write(out, cids, packed, dim=dim, nbits=nbits)
    np.save(out / "centroids.npy", centroids)
    np.save(out / "bucket_cutoffs.npy", np.asarray(codec.bucket_cutoffs))
    np.save(out / "bucket_weights.npy", np.asarray(codec.bucket_weights))
    np.save(out / "doclens.npy", doc_lens.astype(np.int32))
    offsets = np.zeros(n_docs + 1, np.int64)
    np.cumsum(doc_lens, out=offsets[1:])
    np.save(out / "doc_offsets.npy", offsets)

    iv = ivf_mod.build_ivf(cids, token_pids, n_centroids)
    iv.pids.tofile(out / "ivf_pids.bin")
    np.save(out / "ivf_offsets.npy", iv.offsets)

    meta = json.loads((out / "meta.json").read_text())
    meta.update({"n_docs": int(n_docs), "doc_maxlen": int(doc_maxlen),
                 "n_centroids": int(n_centroids)})
    (out / "meta.json").write_text(json.dumps(meta))
    return out


class ColBERTIndex:
    """Loaded index handle. ``mode`` picks the paper's mmap tier or the
    full-RAM baseline for the code/residual pool (everything else —
    centroids, buckets, doclens, IVF — is metadata and stays in RAM in
    both modes, exactly as in the paper)."""

    def __init__(self, path, mode: str = "mmap"):
        self.path = pathlib.Path(path)
        meta = json.loads((self.path / "meta.json").read_text())
        self.meta = meta
        self.n_docs = meta["n_docs"]
        self.doc_maxlen = meta["doc_maxlen"]
        self.dim = meta["dim"]
        self.nbits = meta["nbits"]
        self.n_centroids = meta["n_centroids"]

        self.centroids = np.load(self.path / "centroids.npy")
        self.bucket_cutoffs = np.load(self.path / "bucket_cutoffs.npy")
        self.bucket_weights = np.load(self.path / "bucket_weights.npy")
        self.doclens = np.load(self.path / "doclens.npy")
        self.doc_offsets = np.load(self.path / "doc_offsets.npy")
        ivf_pids = np.fromfile(self.path / "ivf_pids.bin", np.int32)
        ivf_offsets = np.load(self.path / "ivf_offsets.npy")
        self.ivf = ivf_mod.IVF(ivf_pids, ivf_offsets, self.n_centroids)
        self.store = PagedStore(self.path, mode=mode)

    def codec(self) -> residual.ResidualCodec:
        return residual.ResidualCodec(
            centroids=jnp.asarray(self.centroids),
            bucket_cutoffs=jnp.asarray(self.bucket_cutoffs),
            bucket_weights=jnp.asarray(self.bucket_weights),
            nbits=self.nbits)

    def gather_doc_tokens(self, pids: np.ndarray):
        """→ (cids (C, Ld), packed (C, Ld, pd), valid (C, Ld)) for pids
        (host path; goes through the PagedStore and is page-accounted)."""
        pids = np.asarray(pids)
        safe = np.clip(pids, 0, self.n_docs - 1)
        starts = self.doc_offsets[safe]
        cds, res = self.store.gather_ranges(starts, self.doc_maxlen)
        valid = self._doc_valid(pids, safe)
        return cds, res, valid

    def gather_doc_codes(self, pids: np.ndarray):
        """→ (cids (C, Ld), valid (C, Ld)): centroid ids only, for the
        codes-only approximate stage. Touches zero residual pages."""
        pids = np.asarray(pids)
        safe = np.clip(pids, 0, self.n_docs - 1)
        starts = self.doc_offsets[safe]
        cds = self.store.gather_codes_ranges(starts, self.doc_maxlen)
        return cds, self._doc_valid(pids, safe)

    def _doc_valid(self, pids, safe):
        valid = (np.arange(self.doc_maxlen)[None, :] < self.doclens[safe][:, None])
        valid &= (pids >= 0)[:, None]
        return valid
