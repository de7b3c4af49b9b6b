"""The data-plane kernels and the GraphSAGE train step compile for a TPU
v5e chip that is described, not attached.

The interpret-mode kernel tests cannot see what the chip's compiler
refuses (SMEM vector loads, DMA slices not aligned to the (8, 128)
tiling, SMEM capacity), so these compiles run at the widths
``chip_smoke.py`` trains at: the ``reddit --large-scale`` graph, batch
512, fanouts (25, 10), hidden 256.  Nothing runs; a compile that passes
is not a chip run.

The topology is described inside a module fixture (never at import):
only one process may load the TPU compiler library at a time, and every
test worker imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import GNNConfig, GraphSAGE, build_train_step
from repro.core.loader import Minibatch
from repro.kernels import feature_gather as fg
from repro.kernels import neighbor_sample as ns
from repro.kernels.layout import row_width
from repro.optim import adamw

# reddit --large-scale: nodes, edges, feature width; edge block for its
# max degree (1273)
N, E, F, BLOCK_E = 8192, 437_290, 602, 1280
BATCH, FANOUTS = 512, (25, 10)
HOP1 = BATCH * FANOUTS[0]              # hop-1 frontier: 12,800 targets
HOP2 = HOP1 * FANOUTS[1]               # hop-2 ids: 128,000 rows
CACHE_ROWS, CACHE_BLOCKS = 200, 64     # train_pallas_outofcore.json
NB = ns.edge_block_count(E, BLOCK_E)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:      # no TPU compiler library here
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *shapes):
    lowered = jax.jit(fn).lower(*shapes)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


I32, F32 = jnp.int32, jnp.float32
ROWS = (N, 1, row_width(F))
KERNELS = {
    "neighbor_sample": (
        lambda ip, ix, t, r: ns.neighbor_sample(
            ip, ix, t, r, block_e=BLOCK_E, interpret=False),
        [((N + 1,), I32), ((E,), I32), ((HOP1,), I32),
         ((HOP1, FANOUTS[1]), I32)]),
    "neighbor_sample_cached": (
        lambda ip, sl, t, r, c: ns.neighbor_sample_cached(
            ip, sl, t, r, c, block_e=BLOCK_E, max_block=NB - 2,
            interpret=False),
        [((N + 1,), I32), ((NB + 1,), I32), ((HOP1,), I32),
         ((HOP1, FANOUTS[1]), I32), ((CACHE_BLOCKS, 1, BLOCK_E), I32)]),
    "feature_gather_rows": (
        lambda tb, ids: fg.feature_gather_rows(tb, ids, width=F,
                                               interpret=False),
        [(ROWS, F32), ((HOP2,), I32)]),
    "feature_gather_mean": (
        lambda tb, ids: fg.feature_gather_mean(tb, ids, width=F,
                                               interpret=False),
        [(ROWS, F32), ((HOP1, FANOUTS[1]), I32)]),
    "feature_gather_cached": (
        lambda c, so, ids: fg.feature_gather_cached(c, so, ids, width=F,
                                                    interpret=False),
        [((CACHE_ROWS,) + ROWS[1:], F32), ((N + 1,), I32), ((N,), I32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    _compile(fn, *(_shape(one_chip, shape, dtype) for shape, dtype in shapes))


def test_graphsage_train_step_compiles_for_v5e(one_chip):
    gnn = GraphSAGE(GNNConfig(feat_dim=F, fanouts=FANOUTS))
    opt = adamw(1e-3)
    step = build_train_step(None, gnn, opt)

    def init(key):
        params = gnn.init(key)
        return {"params": params, "opt": opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.tree.map(
        lambda s: _shape(one_chip, s.shape, s.dtype),
        jax.eval_shape(init, jax.random.key(0)))
    feats = [_shape(one_chip, (BATCH,) + FANOUTS[:t] + (F,), jnp.float32)
             for t in range(len(FANOUTS) + 1)]
    labels = _shape(one_chip, (BATCH,))

    def train(state, feats, labels):
        return step(state, Minibatch(targets=None, hop_ids=None,
                                     hop_feats=feats, labels=labels))

    lowered = jax.jit(train).lower(state, feats, labels)
    compiled = lowered.compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0
