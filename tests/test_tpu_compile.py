"""Compile the main path for a described TPU v5e, without the chip.

The TPU compiler is installed with jax, so it compiles for a v5e that is
described (``v5e:2x2``) but not attached. That refuses what the Pallas
interpreter accepts and Mosaic does not lower (in-kernel scatters, sub-
tile blocks, SMEM overflow), and it measures each program's HBM use.
Each case compiles at the smoke run's real size (``chip_smoke.py``):
the kernels must contain a Mosaic ``tpu_custom_call`` and every program
must fit one chip's 16 GB. Nothing runs.

The topology is described inside a fixture: only the worker that runs
these tests loads the TPU compiler, and where it cannot be described
the tests skip.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fractals
from repro.core.compact import BlockLayout
from repro.core.compact3d import BlockLayout3D
from repro.core.fractals3d import MENGER
from repro.kernels import squeeze_stencil as sk
from repro.kernels import squeeze_stencil3d as k3
from repro.workloads.rules import GRAY_SCOTT, LIFE, LIFE3D

#: one v5e chip's HBM
HBM_BYTES = 16 * 10 ** 9

#: (fractal, r, m, B) of the smoke's main phase and float-PDE phase
MAIN = (fractals.SIERPINSKI, 17, 4, 4)
PDE = (fractals.CARPET, 8, 2, 1)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    # a compile that cannot be read back without a chip must not land in
    # (and warn from) the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB does not fit one chip"
    return compiled.as_text()


def _state(one_chip, layout, wl, b=None):
    lead = (() if b is None else (b,)) + (
        (wl.n_channels,) if wl.n_channels > 1 else ())
    shape = lead + (layout.n_blocks,) + (layout.rho,) * (
        3 if isinstance(layout, BlockLayout3D) else 2)
    return _sds(one_chip, shape, wl.dtype)


def _table(one_chip, layout, dirs=8):
    return _sds(one_chip, (layout.n_blocks, dirs), jnp.int32)


@pytest.mark.parametrize("case", ["v4-life", "v5-life", "v5-gray-scott"])
def test_2d_kernels_compile(one_chip, case):
    kernel, name = case.split("-", 1)
    wl = LIFE if name == "life" else GRAY_SCOTT
    frac, r, m, b = MAIN if wl is LIFE else PDE
    layout = BlockLayout(frac, r, m)
    k = 3
    if kernel == "v4":
        def fn(s, t):
            return sk._stencil_step_fused_k(layout, s, t, wl, k,
                                            interpret=False)
        state = _state(one_chip, layout, wl)
    else:
        p = layout.macro_tiles(k)[0]

        def fn(s, t):
            return sk._stencil_step_mxu_batched(layout, s, t, wl, k, p,
                                                interpret=False)
        state = _state(one_chip, layout, wl, b)
    assert "tpu_custom_call" in _compile(fn, state,
                                         _table(one_chip, layout))


@pytest.mark.parametrize("kernel", ["fused", "mxu"])
def test_3d_kernels_compile(one_chip, kernel):
    """The smoke runs no 3D phase: one Menger sponge layout with rho=9
    blocks, at the depth whose MXU kernel compiles fastest."""
    layout = BlockLayout3D(MENGER, 5, 2)
    k = 1
    if kernel == "fused":
        def fn(s, t):
            return k3._stencil3d_step_fused_k(layout, s, t, LIFE3D, k,
                                              interpret=False)
    else:
        p = layout.macro_tiles(k)[0]

        def fn(s, t):
            return k3._stencil3d_step_mxu_k(layout, s, t, LIFE3D, k, p,
                                            interpret=False)
    assert "tpu_custom_call" in _compile(
        fn, _state(one_chip, layout, LIFE3D), _table(one_chip, layout, 26))


def test_block_step_r17_batch_fits(one_chip):
    """One fused XLA block launch of the smoke's whole batch at r=17: the
    tables are arguments (not constants) and the windows are chunked,
    so it compiles small and fits."""
    from repro.core.stencil import SqueezeBlockEngine
    frac, r, m, b = MAIN
    eng = SqueezeBlockEngine(BlockLayout(frac, r, m), LIFE)
    k = eng.effective_fusion_k

    def fn(states, table):
        return jax.vmap(lambda s: eng.step_k(s, k, {"table": table}))(
            states)

    text = _compile(fn, _state(one_chip, eng.layout, LIFE, b),
                    _table(one_chip, eng.layout))
    assert "tpu_custom_call" not in text
