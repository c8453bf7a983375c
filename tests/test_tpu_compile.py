"""Compile the serving plane's device programs for a described TPU v5e.

Nothing here runs: each test lowers one jitted program of the main
path at its serving shapes and compiles it with the TPU compiler for a
``v5e:2x2`` topology that is described, not attached — what the chip's
compiler would refuse (unaligned blocks, unsupported layouts, too much
fast memory) fails here at no chip time. The topology is described
inside a module fixture, never at import, so every test worker
collects the same tests and only the worker given this file loads the
TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.fleet_dynamics import ControlParams
from repro.core.placement import SchedulerPolicy
from repro.core.power_model import N_PSTATES, ServerPowerModel
from repro.core.resources import N_RESOURCES
from repro.kernels.forest.forest import (forest_predict_pallas,
                                         resolve_block_t)
from repro.serve import emergency, placement, sharding
from repro.serve.inference import (ForestMeta, PackedForest,
                                   PackedService, ServiceMeta,
                                   served_query)
from repro.sim import fleet
from repro.sim.chassis_sim import paper_chassis_specs

# serving shapes: Fig-7 cluster, Table III forests, batch 256
N_CHASSIS, BLADES, CORES = 60, 12, 40
N_SERVERS = N_CHASSIS * BLADES
BATCH, N_FEATURES, N_TREES, DEPTH = 256, 18, 48, 6
CHASSIS_BUDGET_W = 1860.0
FLEET_CHASSIS, FLEET_STEPS = 1440, 150


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep it out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR",
                                                    "disabled"))
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described "
                            f"here: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _specs(tree, sharding):
    """Arrays -> ShapeDtypeStructs placed by `sharding` (None stays)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def _forest_specs(n_trees, depth, k, sharding):
    td, tl = n_trees * depth, n_trees << depth
    shapes = [(N_FEATURES, td), (1, td), (td, n_trees), (n_trees, tl),
              (tl, k)]
    return [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]


@pytest.mark.parametrize("n_trees,k,block_t,tile", [
    (N_TREES, 2, None, N_TREES), (N_TREES, 5, None, N_TREES),
    (128, 2, 64, 64)])
def test_forest_kernel_compiles(one_chip, n_trees, k, block_t, tile):
    assert resolve_block_t(n_trees, DEPTH, block_t) == tile
    x = jax.ShapeDtypeStruct((BATCH, N_FEATURES), jnp.float32,
                             sharding=one_chip)

    def fn(x, *ops):
        return forest_predict_pallas(x, *ops, n_trees, DEPTH,
                                     block_b=128, block_t=block_t)
    compiled = jax.jit(fn).lower(
        x, *_forest_specs(n_trees, DEPTH, k, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _service(sharding):
    forest = PackedForest(*_forest_specs(N_TREES, DEPTH, 2, sharding))
    fmeta = ForestMeta(N_TREES, DEPTH, "rf")
    return PackedService(*(forest,) * 4), ServiceMeta(
        fmeta, fmeta, fmeta, fmeta, n_features=N_FEATURES)


def test_served_query_pallas_compiles(one_chip):
    packed, meta = _service(one_chip)
    x = jax.ShapeDtypeStruct((BATCH, N_FEATURES), jnp.float32,
                             sharding=one_chip)
    compiled = served_query.lower(packed, meta, x,
                                  kernel="pallas").compile()
    assert compiled.as_text().count("tpu_custom_call") >= 4


def test_sharded_query_pallas_compiles(topo):
    """The sharded pipeline's inference on a 2x2 mesh: the Pallas
    kernel runs inside shard_map, one call per device and forest."""
    mesh = Mesh(np.asarray(topo.devices), (sharding.SHARD_AXIS,))
    packed, meta = _service(NamedSharding(mesh, P()))
    x = jax.ShapeDtypeStruct(
        (BATCH, N_FEATURES), jnp.float32,
        sharding=NamedSharding(mesh, P(sharding.SHARD_AXIS)))
    compiled = sharding._query_fn(meta, "pallas", mesh).lower(
        packed, x).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 4


def _cluster():
    return placement.fresh_state(N_SERVERS, CORES,
                                 np.arange(N_SERVERS) // BLADES)


def test_place_batch_caps_compiles(one_chip):
    ecfg = emergency.EmergencyConfig.from_model(CHASSIS_BUDGET_W)
    state = _specs(_cluster(), one_chip)
    emer = _specs(emergency.init_emergency(N_CHASSIS, xp=jnp), one_chip)
    win = lambda dt: jax.ShapeDtypeStruct(  # noqa: E731
        (1, N_CHASSIS), dt, sharding=one_chip)
    row = lambda dt: jax.ShapeDtypeStruct(  # noqa: E731
        (BATCH,), dt, sharding=one_chip)
    cap = jax.ShapeDtypeStruct((N_CHASSIS, N_RESOURCES), jnp.float32,
                               sharding=one_chip)
    compiled = placement.place_batch_caps.lower(
        state, emer, win(jnp.float32), win(jnp.bool_), win(jnp.float32),
        row(jnp.float32), row(jnp.bool_), row(jnp.float32),
        row(jnp.bool_), cap, SchedulerPolicy(), CORES, ecfg,
        mem_gb=row(jnp.float32)).compile()
    assert compiled.memory_analysis() is not None


def test_fleet_engine_compiles(one_chip):
    layout = fleet.build_layout(paper_chassis_specs(balanced=True))
    la = layout.arrays()
    sc = fleet._scalars(np.full(FLEET_CHASSIS, 2450.0, np.float32),
                        layout.n_servers,
                        np.full(FLEET_CHASSIS, N_PSTATES - 1, np.int32))
    traces = np.zeros((FLEET_CHASSIS, FLEET_STEPS, len(layout.uf_valid)),
                      np.float32)
    cp = ControlParams.from_model(ServerPowerModel(), mode="per_vm")
    engine = fleet._jax_engine(cp, shared_layout=True)
    compiled = engine.lower(_specs(la, one_chip), _specs(sc, one_chip),
                            _specs(traces, one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_home_round_compiles(topo):
    mesh = Mesh(np.asarray(topo.devices), (sharding.SHARD_AXIS,))
    n = len(topo.devices)
    row = NamedSharding(mesh, P(sharding.SHARD_AXIS))
    rep = NamedSharding(mesh, P())
    ecfg = emergency.EmergencyConfig.from_model(CHASSIS_BUDGET_W)
    sh = sharding.shard_state(_cluster(), n, pool_total=1000.0)
    emer = sharding.init_emergency_sharded(N_CHASSIS, n)
    c_loc, b_loc = N_CHASSIS // n, BATCH // n
    win = np.zeros((n, 1, c_loc), np.float32)
    fn = sharding._round_fn(SchedulerPolicy(), float(CORES), mesh, ecfg)
    compiled = fn.lower(
        _specs(sh.shards, row), _specs(sh.pool, row),
        _specs(sh.global_server, row), _specs(sh.res_cap, row),
        _specs(np.zeros((n, b_loc), np.int32), row),
        _specs(np.zeros((n, b_loc), bool), row),
        *_specs((np.zeros(BATCH, np.float32), np.zeros(BATCH, bool),
                 np.zeros(BATCH, np.float32),
                 np.zeros(BATCH, np.float32)), rep),
        _specs(emer, row), _specs(win, row),
        _specs(win.astype(bool), row), _specs(win, row)).compile()
    assert compiled.memory_analysis() is not None
