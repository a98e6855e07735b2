"""The hillclimbed execution paths must be numerically equivalent to
their baselines — the §Perf gains are resharding, not approximation."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess_jax
from repro.configs.registry import ARCHS
from repro.models import layers as L
from repro.models import transformer as T


def test_moe_local_dispatch_equals_global():
    cfg = L.MoECfg(d_model=12, d_ff_expert=16, n_experts=4, top_k=2,
                   capacity_factor=8.0)
    params = L.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 12))
    y_global, _ = L.moe_apply(params, cfg, x, select_threshold=0)
    for slices in (2, 4, 8):
        y_local, _ = L.moe_apply(params, cfg, x, select_threshold=0,
                                 dp_slices=slices)
        np.testing.assert_allclose(np.asarray(y_global),
                                   np.asarray(y_local), rtol=2e-5,
                                   atol=2e-5)


def test_moe_selected_expert_equals_buffer():
    """Low-batch decode path: gathering only routed experts gives the
    same outputs as the full buffer dispatch (no capacity drops)."""
    cfg = L.MoECfg(d_model=10, d_ff_expert=12, n_experts=6, top_k=2,
                   n_shared=1, d_ff_shared=12, capacity_factor=8.0)
    params = L.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 10))  # T·k=8 ≤ 16
    y_sel, aux_sel = L.moe_apply(params, cfg, x)               # select path
    y_buf, aux_buf = L.moe_apply(params, cfg, x, select_threshold=0)
    np.testing.assert_allclose(np.asarray(y_sel), np.asarray(y_buf),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux_sel["aux_loss"]),
                               float(aux_buf["aux_loss"]), rtol=1e-5)


def test_decode_opt_window_slice_matches_full():
    """llama4-style chunked-local decode: the window-slice path scores
    identically to masked full-cache attention."""
    cfg = dataclasses.replace(ARCHS["llama4-maverick-400b-a17b"]
                              .smoke_cfg(), remat="none")
    assert any(b.attn is not None and b.attn.window > 0
               for blocks, _ in cfg.segments for b in blocks)
    params = T.init(jax.random.PRNGKey(0), cfg)
    B, steps = 2, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, steps), 0,
                              cfg.vocab)
    outs = {}
    for opt in (False, True):
        c = dataclasses.replace(cfg, decode_opt=opt)
        caches = T.init_cache(c, B, 32)
        logits_seq = []
        for t in range(steps):
            pos = jnp.full((B, 1), t, jnp.int32)
            logits, caches = T.decode_step(params, c, toks[:, t:t + 1],
                                           pos, caches)
            logits_seq.append(np.asarray(logits))
        outs[opt] = np.stack(logits_seq)
    np.testing.assert_allclose(outs[False], outs[True], rtol=3e-4,
                               atol=3e-4)


def test_sharded_ce_formulation_equals_take_along_axis():
    cfg = ARCHS["qwen3-14b"].smoke_cfg()
    params = T.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    labels = jnp.roll(toks, -1, axis=1).at[0, :3].set(-100)
    base, _ = T.lm_loss(params, cfg, toks, labels)
    cfg2 = dataclasses.replace(cfg, sharded_ce=True)   # no mesh: pure math
    # sharded_ce applies a constraint only when batch_spec is set via
    # P(...); with batch_spec=None P(None, None, 'model') still needs a
    # mesh — emulate the fused formulation directly instead:
    hidden, _ = T.forward(params, cfg, toks)
    logits = T.logits_from_hidden(params, cfg, hidden).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    safe = jnp.maximum(labels, 0)
    onehot = safe[..., None] == jnp.arange(cfg.vocab)
    la = jnp.sum(logits * onehot.astype(logits.dtype), -1)
    mask = (labels >= 0).astype(jnp.float32)
    fused = jnp.sum((lse - la) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    np.testing.assert_allclose(float(base), float(fused), rtol=1e-5)


@pytest.mark.slow
def test_opt_cells_compile_on_small_mesh():
    """The shard_map'd owner-compute cells lower+compile on a (2,2,2)
    multi-pod mesh with smoke configs."""
    out = run_subprocess_jax("""
import dataclasses, jax
from repro.configs.registry import ARCHS
from repro.configs import cells_opt as CO

mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
with mesh:
    arch = ARCHS['colbert-serve']
    cfg = arch.smoke_cfg()
    # pad the smoke index so pool rows divide the mesh
    icfg = dataclasses.replace(cfg.index, n_docs=64, avg_doclen=16)
    cfg = dataclasses.replace(cfg, index=icfg)
    cell = CO.build_plaid_opt(arch, 'serve_plaid', mesh, cfg=cfg,
                              dims={'batch': 4, 'nprobe': 2,
                                    'candidate_cap': 16, 'ndocs': 8})
    jax.jit(cell.fn).lower(*cell.args).compile()
    print('PLAID OPT OK')

    arch = ARCHS['sasrec']
    cfg = dataclasses.replace(arch.smoke_cfg(), n_items=512)
    cell = CO.build_seqrec_retrieval_opt(
        arch, 'retrieval_cand', mesh, cfg=cfg,
        dims={'batch': 1, 'n_candidates': 256})
    jax.jit(cell.fn).lower(*cell.args).compile()
    print('SEQREC OPT OK')
""")
    assert "PLAID OPT OK" in out and "SEQREC OPT OK" in out
