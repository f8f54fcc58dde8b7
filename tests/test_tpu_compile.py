"""The lockstep core compiles for a TPU v5e chip at real widths.

Each case lowers and compiles :func:`repro.kernels.fcfs_core.kernel.
fcfs_core_fwd` for one chip of a *described* ``v5e:2x2`` topology — no
chip attached — at the padded width of an n=8000 ``websearch`` cell on
the default geometry (MAXP 2048, 8 local dies, the ring capacities and
step log that cell buckets to).  It is what the chip's compiler would
refuse (an unsupported primitive, a dtype Mosaic or the TPU cannot
hold, a program that does not fit) caught without chip time; it says
nothing about results or speed.  The topology is described inside a
fixture, so a host that cannot describe it skips these tests and every
other module is untouched.
"""

import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.fcfs_core import ops

#: (lanes, pipelined, prio) — fifo/serial at one cell's 8 lanes, the
#: priority rings pipelined at the fused lane cap, and the ``wide``
#: scatter lowering beyond it.
VARIANTS = [(8, False, False), (64, True, True), (128, False, False)]
MAXP, N_DIES, CAPQ, CAPW, CAPSTEPS = 2048, 8, 256, 32, 32768


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("lanes,pipelined,prio", VARIANTS,
                         ids=["fifo-serial-L8", "prio-pipelined-L64",
                              "wide-L128"])
def test_core_compiles_for_v5e(one_chip, lanes, pipelined, prio):
    with jax.enable_x64(True):
        args = (
            jax.ShapeDtypeStruct((lanes, MAXP, 10), jnp.int64,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((lanes, 3), jnp.int64, sharding=one_chip),
        )
        compiled = ops._core_jit.lower(
            *args, n_dies=N_DIES, capq=CAPQ, capw=CAPW, capsteps=CAPSTEPS,
            pipelined=pipelined, prio=prio,
            wide=lanes > ops._WIDE_LANES).compile()
    mem = compiled.memory_analysis()
    # Far inside one v5e chip's 16 GB.
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 2 ** 30
