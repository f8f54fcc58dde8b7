"""The lockstep core compiles for a TPU v5e chip at real widths.

Each case lowers and compiles :func:`repro.kernels.fcfs_core.kernel.
fcfs_core_fwd` for one chip of a *described* ``v5e:2x2`` topology — no
chip attached — at the padded width of an n=8000 ``websearch`` cell on
the default geometry (MAXP 2048, 8 local dies, the ring capacities and
step log that cell buckets to), in the carry lowering a TPU dispatch
takes (:func:`repro.kernels.fcfs_core.ops.carry_lowering`).  It is what
the chip's compiler would refuse (an unsupported primitive, a dtype
Mosaic or the TPU cannot hold, a program that does not fit) caught
without chip time, and the shape of the compiled loop body: the
``"select"`` lowering has no per-lane code.  It says nothing about
results or speed.  The topology is described inside a fixture, so a
host that cannot describe it skips these tests and every other module
is untouched.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.fcfs_core import ops

#: (lanes, pipelined, prio) — fifo/serial at one cell's 8 lanes, the
#: priority rings pipelined at the fused lane cap, and beyond it.
VARIANTS = [(8, False, False), (64, True, True), (128, False, False)]
MAXP, N_DIES, CAPQ, CAPW, CAPSTEPS = 2048, 8, 256, 32, 32768

#: Instructions that compute nothing in a compiled loop body.
_TRIVIAL = {"parameter", "tuple", "get-tuple-element", "constant",
            "bitcast"}


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


@pytest.fixture(scope="module")
def compile_core(one_chip):
    """``(lanes, pipelined, prio, lowering) -> compiled core``, each
    variant compiled once per module."""
    done = {}

    def compile_(lanes, pipelined, prio, lowering):
        key = (lanes, pipelined, prio, lowering)
        if key not in done:
            with jax.enable_x64(True):
                args = (
                    jax.ShapeDtypeStruct((lanes, MAXP, 10), jnp.int64,
                                         sharding=one_chip),
                    jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
                    jax.ShapeDtypeStruct((lanes, 3), jnp.int64,
                                         sharding=one_chip),
                )
                done[key] = ops._core_jit.lower(
                    *args, n_dies=N_DIES, capq=CAPQ, capw=CAPW,
                    capsteps=CAPSTEPS, pipelined=pipelined, prio=prio,
                    lowering=lowering).compile()
        return done[key]

    return compile_


def _loop_body(hlo_text):
    """Opcodes of the one while loop's body computation in compiled HLO
    text."""
    bodies = re.findall(r"\bwhile\(.*?\bbody=%?([\w.\-]+)", hlo_text)
    assert len(bodies) == 1, bodies
    lines = hlo_text.splitlines()
    head = re.compile(rf"^%?{re.escape(bodies[0])} ")
    start = next(i for i, l in enumerate(lines) if head.match(l))
    opcodes = []
    for line in lines[start + 1:]:
        if line.startswith("}"):
            break
        # ``%name = <shape> <opcode>(...)``: the first lower-case word
        # followed by "(" after the "=" (shapes hold only upper-case
        # layout tags such as ``T(8,128)``).
        m = re.search(r"\s([a-z][a-z0-9\-]*)\(", line.split(" = ", 1)[1])
        opcodes.append(m.group(1))
    return opcodes


@pytest.mark.parametrize("lanes,pipelined,prio", VARIANTS,
                         ids=["fifo-serial-L8", "prio-pipelined-L64",
                              "wide-L128"])
def test_core_compiles_for_v5e(compile_core, lanes, pipelined, prio):
    compiled = compile_core(lanes, pipelined, prio,
                            ops.carry_lowering("tpu", lanes))
    mem = compiled.memory_analysis()
    # Far inside one v5e chip's 16 GB.
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("lanes,pipelined,prio",
                         [(8, False, False), (24, False, False),
                          (64, True, True), (128, False, False)],
                         ids=["L8", "L24", "prio-pipelined-L64", "L128"])
def test_select_body_has_no_per_lane_code(compile_core, lanes, pipelined,
                                          prio):
    # The per-lane unroll writes each carry buffer with one
    # dynamic-update-slice per lane (several per lane once x64 splits
    # the int64 buffers): 42 at 8 lanes, 322 at 64.  The select body
    # keeps two (the step log's halves), and its size does not follow
    # the lane count.
    body = _loop_body(compile_core(lanes, pipelined, prio,
                                   "select").as_text())
    assert body.count("dynamic-update-slice") <= 2
    work = sum(op not in _TRIVIAL for op in body)
    base = sum(op not in _TRIVIAL for op in _loop_body(
        compile_core(8, False, False, "select").as_text()))
    assert abs(work - base) <= 10, (work, base)


def test_unrolled_body_counts_its_lanes(compile_core):
    # The count above can see a per-lane unroll: the CPU lowering,
    # compiled for the chip, holds a dynamic-update-slice per lane.
    body = _loop_body(compile_core(8, False, False, "unrolled").as_text())
    assert body.count("dynamic-update-slice") >= 8 * 3
