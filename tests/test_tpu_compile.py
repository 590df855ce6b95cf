"""Compile the device path for a described TPU v5e, with no chip attached.

The TPU compiler is installed beside jax, and it compiles for a chip that
is described, not attached: what it refuses here it would refuse on the
chip.  The topology is described inside a module fixture — never while a
module is imported — because only one process at a time may load the TPU
library, and every test worker imports every test file.

The heap scan (``_heap_runner``, the ``lax`` step: times as int64 float64
bit patterns, under x64), which runs every jax lane on the device,
compiles at the launch shapes of the benchmark's three cells, at the
floor and the lane cap of a launch, and at the ``bs=64`` sizes of both
applications, with its loop's state kept in vector memory wherever the
compiler can hold it there.
"""
import functools
import re

import pytest

import jax
import jax.numpy as jnp

from repro.core.jaxsim import (HEAP_MIN_COHORTS, HEAP_MIN_LANES,
                               HEAP_ROW_ALIGN, MEGABATCH_CHUNK, _heap_runner)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        # the TPU compiler ships with the installed jax: a topology that
        # cannot be described is a bring-up failure, so it fails the tests
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _sd(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


#: Heap-scan launches: padded rows, cohorts, options, device kinds, pools,
#: slots, lanes.  Each is read from ``_heap_cohorts``'s launch arguments in
#: a CPU sweep of the configuration named: rows pad to a multiple of
#: ``HEAP_ROW_ALIGN``, cohorts and lanes bucket to powers of two no
#: smaller than ``HEAP_MIN_COHORTS`` and ``HEAP_MIN_LANES`` (lanes capped
#: by ``MEGABATCH_CHUNK``), slots to the widest lane's clamped need.
HEAP_LAUNCHES = {
    # the benchmark's cells, where every lane of a group of at least
    # MIN_LOCKSTEP goes to the heap scan
    "matmul1024-ramp": (3584, 4, 2, 4, 4, 64, 128),
    "chol2048-dgemm64": (4480, 4, 2, 4, 4, 64, 256),
    "chol2048-fig9": (4992, 8, 2, 5, 5, 8, 64),
    # the floor: six synth candidates (synth_trace(12), +SMP, 1-6
    # accelerators) fill none of the floors
    "floor": (HEAP_ROW_ALIGN, HEAP_MIN_COHORTS, 2, 4, 4, 8, HEAP_MIN_LANES),
    # fig9's rows and cohorts at the lane cap
    "chol2048-fig9-cap": (4992, 8, 2, 5, 5, 8, MEGABATCH_CHUNK),
    # the two configurations at bs=64: matmul1024-ramp (4,096 tasks) and
    # the Fig. 9 grid of chol2048-fig9 (5,984 kernel tasks)
    "matmul1024-bs64": (28672, 4, 2, 4, 4, 64, 128),
    "chol2048-bs64": (38784, 8, 2, 5, 5, 8, 64),
}

#: Launches whose ``[rows, lanes]`` state the compiler cannot hold in
#: vector memory: their loop copies it in from HBM and back every step.
STATE_COPIED = {"chol2048-bs64"}

#: Argument bytes a launch stays under: 1 GiB, but for Cholesky at bs=64,
#: whose successor bitsets ``[R, G, R / 32]`` alone are 1.50 GB.
ARG_LIMIT = {"chol2048-bs64": 2 << 30}


@pytest.mark.parametrize("name", HEAP_LAUNCHES)
def test_heap_runner_compiles_for_v5e(one_chip, name):
    """The heap scan at a launch shape: it compiles, its loop copies
    nothing between HBM and vector memory (but where its state cannot fit
    there), and its step input is stacked over the padded rows."""
    R, G, K, NK, P, S, B = HEAP_LAUNCHES[name]
    sd = functools.partial(_sd, one_chip)
    with jax.enable_x64(True):
        tabs = {"npred": sd((R, G), jnp.int32), "c": sd((R, G), jnp.int32),
                "is_comp": sd((R, G), jnp.bool_),
                "k_first": sd((R, G), jnp.int32),
                "opts": sd((R, G, K), jnp.int32),
                "cost": sd((R, G, NK), jnp.int64),
                "act": sd((R, G, NK), jnp.bool_),
                "bad_row": sd((R, G), jnp.bool_),
                "succ": sd((R, G, R // 32), jnp.uint32)}
        compiled = jax.jit(_heap_runner()).lower(
            tabs, sd((P, S, B), jnp.int64), sd((B, NK), jnp.int32),
            sd((B,), jnp.int32), sd((B,), jnp.int32), sd((B,), jnp.int32),
            sd((), jnp.bool_)).compile()
    hlo = compiled.as_text()
    body = re.search(r"while\([^)]*\), condition=%?[\w.\-]+, "
                     r"body=%?([\w.\-]+)", hlo).group(1)
    start = hlo.index(f"%{body} ")
    loop = hlo[start:hlo.index("\n}\n", start)]
    copies = "copy-start" in loop or "copy-done" in loop
    assert copies == (name in STATE_COPIED)
    assert f"pred[{R},{B}]" in hlo
    assert 0 < compiled.memory_analysis().argument_size_in_bytes \
        < ARG_LIMIT.get(name, 1 << 30)
