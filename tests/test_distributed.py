"""Multi-device integration tests (subprocess with 8 fake CPU devices):
pjit train parity vs single device, elastic checkpoint re-shard,
compressed cross-pod psum, sharding-rule coverage, dry-run micro-cell,
HLO analyzer ground truth."""

import pytest

from conftest import run_subprocess_jax

pytestmark = pytest.mark.slow


def test_pjit_train_matches_single_device():
    """The same train step on a (2,4) mesh and on 1 device produces the
    same loss trajectory — sharding must not change numerics."""
    out = run_subprocess_jax("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.training.optimizer import AdamWCfg, adamw_init, adamw_update

W = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
def loss_fn(params, batch):
    p = jnp.tanh(batch['x'] @ params['w1']) @ params['w2']
    return jnp.mean((p - batch['y'])**2)

def trajectory(mesh=None):
    params = {'w1': jnp.zeros((16, 16)) + 0.01, 'w2': jnp.zeros((16, 8)) + 0.01}
    cfg = AdamWCfg(lr=0.05, weight_decay=0.0, warmup_steps=0, total_steps=100, min_lr_frac=1.0)
    state = adamw_init(params, cfg)
    if mesh is not None:
        sh = NamedSharding(mesh, P('data', None))
        rep = NamedSharding(mesh, P())
        params = jax.tree.map(lambda x: jax.device_put(x, rep), params)
    @jax.jit
    def step(params, state, batch):
        g = jax.grad(loss_fn)(params, batch)
        return adamw_update(g, state, params, cfg)[:2]
    losses = []
    for s in range(8):
        k = jax.random.PRNGKey(s)
        x = jax.random.normal(k, (32, 16)); y = jnp.tanh(x @ W[:, :16][:, :16])[:, :8]
        batch = {'x': x, 'y': y}
        if mesh is not None:
            batch = {k2: jax.device_put(v, NamedSharding(mesh, P('data', None))) for k2, v in batch.items()}
        losses.append(float(loss_fn(params, batch)))
        params, state = step(params, state, batch)
    return losses

l1 = trajectory(None)
mesh = jax.make_mesh((4, 2), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with mesh:
    l2 = trajectory(mesh)
np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-6)
print('PARITY OK')
""")
    assert "PARITY OK" in out


def test_elastic_restore_across_meshes():
    """Checkpoint written under a (4,2) mesh restores onto (2,4) and a
    single device — elastic re-shard on restore."""
    out = run_subprocess_jax("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.training import checkpoint as C

tree = {'w': jax.random.normal(jax.random.PRNGKey(0), (8, 16)),
        'b': jnp.arange(16.0)}
mesh_a = jax.make_mesh((4, 2), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
sh_a = {'w': NamedSharding(mesh_a, P('data', 'model')), 'b': NamedSharding(mesh_a, P('model'))}
placed = jax.tree.map(lambda x, s: jax.device_put(x, s), tree, sh_a)
with tempfile.TemporaryDirectory() as d:
    C.save_checkpoint(d, 3, placed)
    mesh_b = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh_b = {'w': NamedSharding(mesh_b, P('model', 'data')), 'b': NamedSharding(mesh_b, P())}
    step, restored = C.load_checkpoint(d, template=tree, shardings=sh_b)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored['w']), np.asarray(tree['w']))
    assert restored['w'].sharding == sh_b['w']
    step, single = C.load_checkpoint(d, template=tree)
    np.testing.assert_array_equal(np.asarray(single['b']), np.asarray(tree['b']))
print('ELASTIC OK')
""")
    assert "ELASTIC OK" in out


def test_q8_psum_across_pod_axis():
    """int8-compressed all-reduce over a real 8-way axis ≈ exact psum."""
    out = run_subprocess_jax("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map
from repro.training.compression import q8_psum
mesh = jax.make_mesh((8,), ('pod',),
                     axis_types=(jax.sharding.AxisType.Auto,) * 1)
x = jax.random.normal(jax.random.PRNGKey(0), (8, 4, 256))
exact = jnp.sum(x, axis=0)
f = shard_map(lambda v: q8_psum(v[0], 'pod'), mesh=mesh,
              in_specs=P('pod'), out_specs=P())
approx = f(x)
rel = float(jnp.max(jnp.abs(approx - exact)) / jnp.max(jnp.abs(exact)))
assert rel < 0.05, rel
print('Q8PSUM OK', rel)
""")
    assert "Q8PSUM OK" in out


def test_dryrun_micro_cell_compiles_multipod():
    """A miniature multi-pod mesh (2,2,2) lowers + compiles an LM smoke
    train cell with the production sharding rules and shows the
    expected collectives."""
    out = run_subprocess_jax("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.registry import ARCHS
from repro.configs.cells import build_cell
from repro.launch import hlo_analysis

mesh = jax.make_mesh((2, 2, 2), ('pod', 'data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
arch = ARCHS['qwen3-14b']
with mesh:
    cell = build_cell(arch, 'train_4k', mesh, cfg=arch.smoke_cfg(),
                      dims={'global_batch': 8, 'seq': 32})
    compiled = jax.jit(cell.fn, donate_argnums=cell.donate_argnums).lower(*cell.args).compile()
costs = hlo_analysis.analyze(compiled.as_text(), n_devices=8)
assert costs.flops > 0
assert costs.coll_bytes > 0, 'expected gradient all-reduce traffic'
print('MICROCELL OK', costs.flops, costs.coll_by_kind)
""")
    assert "MICROCELL OK" in out


def test_hlo_analyzer_scan_ground_truth():
    """Analyzer reproduces the analytic FLOPs of a scanned matmul
    (trip-count × per-layer dot) exactly."""
    out = run_subprocess_jax("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_analysis import analyze
mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
def f(ws, x):
    y, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)
    return y
ws = jax.ShapeDtypeStruct((12, 512, 512), jnp.float32, sharding=NamedSharding(mesh, P(None, None, 'model')))
x = jax.ShapeDtypeStruct((256, 512), jnp.float32, sharding=NamedSharding(mesh, P('data', None)))
with mesh:
    compiled = jax.jit(f).lower(ws, x).compile()
c = analyze(compiled.as_text(), n_devices=8)
expected = 12 * 2 * 128 * 512 * 128     # per-device
assert abs(c.flops - expected) / expected < 1e-6, (c.flops, expected)
assert c.coll_by_kind.get('all-gather', 0) > 0
print('ANALYZER OK')
""")
    assert "ANALYZER OK" in out


def test_recsys_sharded_lookup_matches_replicated():
    out = run_subprocess_jax("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.models.recsys import embedding as EB
mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
table = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
ids = jax.random.randint(jax.random.PRNGKey(1), (16, 3), 0, 64)
with mesh:
    t_sh = jax.device_put(table, NamedSharding(mesh, P('model', None)))
    i_sh = jax.device_put(ids, NamedSharding(mesh, P('data', None)))
    out_sh = jax.jit(lambda t, i: EB.lookup(t, i, shard_axis='model'))(t_sh, i_sh)
np.testing.assert_allclose(np.asarray(out_sh), np.asarray(table)[np.asarray(ids)], rtol=1e-6)
print('LOOKUP OK')
""")
    assert "LOOKUP OK" in out


def test_pipeline_parallel_matches_sequential():
    """GPipe fill-drain over a 4-stage 'pipe' axis == applying the 4
    stages sequentially."""
    out = run_subprocess_jax("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline_parallel import (bubble_fraction,
                                                 make_pipelined_fn)
S, M, mb, d = 4, 8, 2, 16
mesh = jax.make_mesh((S,), ('pipe',),
                     axis_types=(jax.sharding.AxisType.Auto,) * 1)
ws = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) * 0.3
bs = jax.random.normal(jax.random.PRNGKey(1), (S, d)) * 0.1
params = {'w': ws, 'b': bs}
xs = jax.random.normal(jax.random.PRNGKey(2), (M, mb, d))

def stage_fn(p, x):
    return jnp.tanh(x @ p['w'] + p['b'])

with mesh:
    piped = jax.jit(make_pipelined_fn(stage_fn, mesh, n_stages=S))
    got = piped(params, xs)

ref = xs
for s in range(S):
    ref = jnp.tanh(ref @ ws[s] + bs[s])
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)
assert abs(bubble_fraction(S, M) - 3/11) < 1e-9
print('PIPELINE OK')
""", n_devices=4)
    assert "PIPELINE OK" in out
