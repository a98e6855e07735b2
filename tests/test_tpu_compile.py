"""Compile the served Pallas kernels for a TPU v5e that is described,
not attached: Mosaic refuses here what the chip's compiler would refuse
(block shapes off the (8, 128) tiling, VMEM over the limit), at no chip
time. Shapes are the served ones: a micro-batch of 8 queries of 32
128-d tokens, ``first_k`` = 200 candidates of up to 180 tokens, 2^15 and
2^17 centroids, and a SPLADE stage 1 over a 30,522-term vocabulary and
65,536 passages.

The topology is described only inside the module fixture, never at
import: one process at a time may load the TPU library, and every test
worker imports this file."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.plaid import fused_hybrid_tail
from repro.index.live import DELTA_WIDTH
from repro.index.splade_device import _score_topk
from repro.kernels.decompress_maxsim.ops import decompress_maxsim_scores_batch
from repro.kernels.fused_rerank.ops import fused_rerank_topk_batch

B, C, LD, LQ, D = 8, 200, 180, 32, 128
VOCAB, N_DOCS, QT, MAX_DF = 30_522, 65_536, 32, 4_096


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler to describe one
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _rerank_args(sharding, nbits, K):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return dict(q=s((B, LQ, D), jnp.float32),
                packed=s((B, C, LD, D * nbits // 8), jnp.uint8),
                cids=s((B, C, LD), jnp.int32),
                valid=s((B, C, LD), jnp.bool_),
                cand_mask=s((B, C), jnp.bool_),
                q_valid=s((B, LQ), jnp.bool_),
                centroids=s((K, D), jnp.float32),
                bucket_weights=s((1 << nbits,), jnp.float32))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _compile_tail(sharding, program, nbits, K):
    """Compile one served tail program at the module's shapes."""
    a = _rerank_args(sharding, nbits, K)
    common = (a["q"], a["packed"], a["cids"], a["valid"], a["cand_mask"],
              a["centroids"], a["bucket_weights"])
    if program == "fused_hybrid_tail":
        lowered = fused_hybrid_tail.lower(
            *common, a["q_valid"],
            jax.ShapeDtypeStruct((B, C), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((B,), jnp.float32, sharding=sharding),
            nbits=nbits, k=100, b=B, normalizer="znorm", impl="pallas")
    else:
        lowered = fused_rerank_topk_batch.lower(
            *common, q_valid=a["q_valid"], nbits=nbits, k=100,
            impl="pallas")
    return lowered.compile()


RERANK_CASES = [(4, 1 << 15), (4, 1 << 17), (2, 1 << 15)]


@pytest.mark.parametrize("nbits,K", RERANK_CASES)
def test_fused_rerank_compiles_for_v5e(one_chip, nbits, K):
    _assert_kernel(_compile_tail(one_chip, "fused_rerank_topk_batch",
                                 nbits, K))


@pytest.mark.parametrize("nbits,K", RERANK_CASES)
def test_decompress_maxsim_compiles_for_v5e(one_chip, nbits, K):
    a = _rerank_args(one_chip, nbits, K)
    compiled = decompress_maxsim_scores_batch.lower(
        a["q"], a["packed"], a["cids"], a["valid"], a["centroids"],
        a["bucket_weights"], q_valid=a["q_valid"], nbits=nbits,
        impl="pallas").compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("b", [1, B])
def test_narrow_delta_dispatch_compiles_for_v5e(one_chip, b):
    """The live overlay's delta dispatch at its narrow candidate width
    (``DELTA_WIDTH``, under one 128-lane tile) for the smallest and the
    largest padded micro-batch."""
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    nbits, w = 2, DELTA_WIDTH
    compiled = decompress_maxsim_scores_batch.lower(
        s((b, LQ, D), jnp.float32), s((b, w, LD, D * nbits // 8), jnp.uint8),
        s((b, w, LD), jnp.int32), s((b, w, LD), jnp.bool_),
        s((1 << 15, D), jnp.float32), s((1 << nbits,), jnp.float32),
        q_valid=s((b, LQ), jnp.bool_), nbits=nbits, impl="pallas").compile()
    _assert_kernel(compiled)


def test_hybrid_tail_compiles_for_v5e(one_chip):
    _assert_kernel(_compile_tail(one_chip, "fused_hybrid_tail", 4, 1 << 15))


# a float32 gather whose slices are single elements: one index per float
SCALAR_GATHER = re.compile(r"= f32\[[^\n]*? gather\([^\n]*?"
                           r"slice_sizes=\{1(?:,1)*\}")


@pytest.mark.parametrize("program", ["fused_hybrid_tail",
                                     "fused_rerank_topk_batch"])
def test_tail_gathers_centroid_scores_by_rows(one_chip, program):
    """The ``q·c`` operand is gathered as whole rows of the centroid-score
    table, one index per document token: no float32 gather of single
    elements in either served tail program."""
    text = _compile_tail(one_chip, program, 2, 1 << 15).as_text()
    assert " gather(" in text
    found = SCALAR_GATHER.search(text)
    assert found is None, found[0]


def test_splade_stage1_compiles_for_v5e(one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = _score_topk.lower(
        s((VOCAB, MAX_DF), jnp.int32), s((VOCAB, MAX_DF), jnp.uint8),
        s((B, QT), jnp.int32), s((B, QT), jnp.float32), s((), jnp.float32),
        n_docs=N_DOCS, k=200, impl="pallas", block_d=2048,
        chunk=512).compile()
    _assert_kernel(compiled)
