"""Compile the engines' Pallas kernels for a described TPU v5e, no chip.

The TPU compiler is installed even where no chip is attached; compiling
for a *described* ``v5e:2x2`` topology raises whatever Mosaic would raise
on the chip (block shapes, unsupported lowerings, scoped-VMEM overflow),
at the widths ``chip_smoke.py`` runs: 2^20 keys, a 2^19-slot pad, a trace
streamed in chunks, MPL 72.  Nothing runs, so results and times are not
checked here.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.cache import flat  # noqa: E402
from repro.core import lru_network  # noqa: E402
from repro.core.simspec import compile_network  # noqa: E402
from repro.kernels import event_sim, replay  # noqa: E402

KEY_SPACE = 1 << 20
PAD = 1 << 19
LANES = 16                       # 8 capacities x 2 seeds
REQUESTS = 3 * replay.CHUNK + 100  # several chunks and a ragged tail
SIM_LANES = 64                   # 16 p_hit x 4 seeds


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # the pinned installation ships the TPU compiler: failing to describe
    # the chip is a failure, never a skip
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("policy", ["lru", "sieve", "s3fifo"])
def test_replay_kernel_compiles(one_chip, policy):
    """An O(1) policy, the hand-scan SIEVE, and S3-FIFO — the one whose
    five slot tables need more than the default scoped VMEM."""
    args = [_sds(s, d, one_chip) for s, d in (
        ((LANES, flat.N_PARAMS), jnp.int32), ((LANES,), jnp.float32),
        ((LANES, REQUESTS), jnp.int32), ((LANES, REQUESTS), jnp.float32),
        ((LANES, REQUESTS), jnp.int32))]
    compiled = replay.pallas_grid.lower(
        policy, *args, key_space=KEY_SPACE, pad=PAD).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("trace_cap", [0, 128])
def test_event_kernel_compiles(one_chip, trace_cap):
    net = lru_network(disk_us=100.0)
    assert net.mpl == 72
    spec = compile_network(net, 0.9)
    tables = {
        event_sim.IS_QUEUE: np.asarray(spec.is_queue, np.int32),
        event_sim.SVC_NS: spec.svc_ns, event_sim.DIST_ID: spec.dist_id,
        event_sim.DIST_PAR: spec.dist_params,
        event_sim.BRANCH_CUM: spec.branch_cum, event_sim.VISITS: spec.visits,
        event_sim.SERVERS: spec.servers,
    }
    tabs = {k: _sds((SIM_LANES, np.asarray(a).size), np.asarray(a).dtype,
                    one_chip) for k, a in tables.items()}
    bmiss = (_sds((SIM_LANES, spec.branch_cum.shape[0]), jnp.int32, one_chip)
             if trace_cap else None)
    n_req = 200_000
    route_len = int(spec.visits.shape[-1])
    compiled = event_sim.pallas_grid.lower(
        tabs, _sds((SIM_LANES,), jnp.int32, one_chip), bmiss,
        n_requests=n_req, warmup=n_req // 4, mpl=net.mpl,
        max_events=n_req * (route_len + 2) * 3, route_len=route_len,
        trace_cap=trace_cap).compile()
    assert "tpu_custom_call" in compiled.as_text()
