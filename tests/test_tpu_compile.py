"""Compile the device path for a described TPU v5e, with no chip attached.

The TPU compiler is installed beside jax, and it compiles for a chip that
is described, not attached: what it refuses here it would refuse on the
chip.  The topology is described inside a module fixture — never while a
module is imported — because only one process at a time may load the TPU
library, and every test worker imports every test file.

* The jax engine's scan runner (the ``lax`` step: times as int64 float64
  bit patterns, under x64) compiles at full-size slice shapes: the
  Cholesky ``n=2048, bs=128`` graph (4,912 task rows after DMA
  augmentation, 5 pools), and a ``synth:1024`` sweep over 1-128
  accelerators (5,120 task rows, 4 pools, slot axes of 32, 64 and 128).
* The heap scan (``_heap_runner``), which runs every megabatch lane,
  compiles at the launch shapes of the benchmark's three cells, with no
  copy between HBM and vector memory inside its loop.
* The pallas step-commit kernel compiles to a Mosaic custom call with
  float32 state and x64 off.  No 64-bit operand passes into a Mosaic
  kernel, which is why ``step_impl="auto"`` resolves to ``lax`` inside
  the scan, and ``"pallas"`` raises.
"""
import functools

import pytest

import jax
import jax.numpy as jnp

from repro.core.devices import zynq_system
from repro.core.jaxsim import (_heap_runner, _runner, resolve_step_impl,
                               simulate_jax)
from repro.kernels.lockstep_step import step_commit
from repro.testing.synth import frozen_for, synth_trace

#: Full-size scan slices: task rows (T = n), pools, slots, lanes, per-row
#: device options, device kinds and predecessor-list width.
SLICES = {"phaseA": (4912, 5, 8, 32, 2, 5, 10),
          "phaseB-S32": (5120, 4, 32, 64, 2, 4, 4),
          "phaseB-S64": (5120, 4, 64, 32, 2, 4, 4),
          "phaseB-S128": (5120, 4, 128, 16, 2, 4, 4)}


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


@pytest.mark.parametrize("shape", SLICES.values(), ids=SLICES.keys())
def test_scan_runner_compiles_for_v5e(one_chip, shape):
    T, P, S, B, K, NK, PC = shape
    sd = functools.partial(_sd, one_chip)
    t64 = jnp.int64                     # times travel as float64 bits
    with jax.enable_x64(True):
        xs = {"valid": sd((T, B), jnp.bool_),
              "tb": sd((T, B), jnp.int64), "c": sd((T, B), jnp.int32),
              "is_comp": sd((T, B), jnp.bool_),
              "k_first": sd((T, B), jnp.int32),
              "own_opts": sd((T, B, K), jnp.int32),
              "own_cost": sd((T, B, NK), t64),
              "par_opts": sd((T, B, K), jnp.int32),
              "par_cost": sd((T, B, NK), t64),
              "act": sd((T, B, NK), jnp.bool_),
              "bad_row": sd((T, B), jnp.bool_),
              "pred": sd((T, B, PC), jnp.int32),
              "at_own": sd((T, B), jnp.int32),
              "at_par": sd((T, B), jnp.int32)}
        args = (xs, sd((P, S, B), t64),                  # clocks
                sd((T + 1, B), t64),                     # ends
                sd((2 * T + 1, B), jnp.int32),           # placed
                sd((P, B), t64), sd((P, B), jnp.bool_),  # busy, seen
                sd((B, NK), jnp.int32), sd((B,), jnp.int32),
                sd((), jnp.bool_))
        compiled = jax.jit(_runner()).lower(*args).compile()
    mem = compiled.memory_analysis()
    # the per-slice step inputs dominate: ~43 MB at 64 lanes, far inside
    # the chip's 16 GB
    assert 0 < mem.argument_size_in_bytes < 1 << 30


#: Heap-scan launches of the benchmark's cells, where every lane of a
#: group of at least ``MIN_LOCKSTEP`` goes to the heap scan: padded rows,
#: cohorts, options, device kinds, pools, slots, lanes (read from
#: ``_heap_cohorts``'s launch arguments in a CPU sweep of each cell).
HEAP_LAUNCHES = {"matmul1024-ramp": (3584, 4, 2, 4, 4, 64, 128),
                 "chol2048-dgemm64": (4480, 4, 2, 4, 4, 64, 256),
                 "chol2048-fig9": (4992, 8, 2, 5, 5, 8, 64)}


@pytest.mark.parametrize("shape", HEAP_LAUNCHES.values(),
                         ids=HEAP_LAUNCHES.keys())
def test_heap_runner_compiles_for_v5e(one_chip, shape):
    """The heap scan at the benchmark's launch shapes: it compiles, its
    loop copies nothing between HBM and vector memory, and its step input
    is stacked over the padded rows."""
    import re
    R, G, K, NK, P, S, B = shape
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
    assert "copy-start" not in loop and "copy-done" not in loop
    assert f"pred[{R},{B}]" in hlo
    assert 0 < compiled.memory_analysis().argument_size_in_bytes < 1 << 30


@pytest.mark.parametrize("P,S,B", [(4, 32, 64), (5, 8, 256)])
def test_step_commit_kernel_compiles_for_v5e(one_chip, P, S, B):
    sd = functools.partial(_sd, one_chip)
    args = (sd((P, S, B), jnp.float32), sd((P, B), jnp.float32),
            sd((P, B), jnp.bool_), sd((B,), jnp.int32),
            sd((B,), jnp.float32), sd((B,), jnp.float32),
            sd((B,), jnp.bool_))
    with jax.enable_x64(False):
        compiled = jax.jit(functools.partial(step_commit, interpret=False)) \
            .lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_step_impl_raises_never_interprets(monkeypatch):
    assert resolve_step_impl("auto") == "lax"
    with pytest.raises(ValueError, match="backend is 'cpu'"):
        resolve_step_impl("pallas")
    fg, _ = frozen_for(synth_trace(8), smp=False)
    systems = [zynq_system(f"{n}acc", {"fpga:k": n}) for n in range(1, 7)]
    with pytest.raises(ValueError, match="step_impl='pallas'"):
        simulate_jax(fg, systems, step_impl="pallas")
    # on a TPU backend the scan's 64-bit state is the reason
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_step_impl("auto") == "lax"
    with pytest.raises(ValueError, match="64-bit state"):
        resolve_step_impl("pallas")
