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
import json
import os
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

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
def topo():
    from jax.experimental import topologies
    # a compile that cannot be read back without a chip must not land in
    # (and warn from) the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return _fits(jax.jit(fn).lower(*args).compile())


def _fits(compiled):
    """The compiled program's text, once its memory (per device, for a
    sharded program) is known to fit one chip."""
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


#: the one-chip benchmark cells' step programs: (workload, fractal, r, m,
#: traffic mix); the runner maps the block engine over each batch, whose
#: per-row states never ask for the word view
ROW_GATHER_CELLS = {
    "life-r17-batch4": (LIFE, fractals.SIERPINSKI, 17, 4,
                        "closed-c4-r17-s64"),
    "grayscott-carpet-r9-batch2": (GRAY_SCOTT, fractals.CARPET, 9, 2,
                                   "closed-c2-r9-s32")}


def _warmed_batches():
    """(cell, batch rows) for every batch size the benchmark warms in
    each one-chip cell (``chipbench.loadgen.warm_sizes``)."""
    from chipbench import loadgen
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = []
    for cell, (*_, mix) in ROW_GATHER_CELLS.items():
        with open(os.path.join(root, "chipbench", "traffic",
                               mix + ".json")) as f:
            traffic = json.load(f)
        out += [(cell, b) for b in loadgen.warm_sizes(
            traffic, int(traffic["clients"]))]
    return out


@pytest.mark.parametrize("cell,b", _warmed_batches())
def test_one_chip_cell_steps_gather_rows(one_chip, cell, b):
    """The runner's run program for each batch a one-chip cell warms
    keeps the row gather: no gather of 32-bit words."""
    from repro.core.stencil import SqueezeBlockEngine
    wl, frac, r, m, _ = ROW_GATHER_CELLS[cell]
    eng = SqueezeBlockEngine(BlockLayout(frac, r, m), wl)
    k = eng.effective_fusion_k

    def run(states, table):
        body = jax.vmap(lambda s: eng.step_k(s, k, {"table": table}))
        return jax.lax.fori_loop(0, 2, lambda _, s: body(s), states)

    text = _compile(run, _state(one_chip, eng.layout, wl, b),
                    _table(one_chip, eng.layout))
    assert "tpu_custom_call" not in text
    assert not re.search(r"= u32\[[\d,]+\]\S* gather\(", text)


#: one row of each benchmark cell's shape: Life r=17 (u8, block axis on
#: the lanes, four cells to a word), and Gray-Scott's two channels at
#: r=9 on the carpet in bfloat16 (its f32 rows take the plain copy; 9
#: cells a block row leave a word straddling two rows)
HANDOFF_ROWS = {"life-r17": (LIFE, fractals.SIERPINSKI, 17, 4, jnp.uint8),
                "gray-scott-r9-bf16": (GRAY_SCOTT, fractals.CARPET, 9, 2,
                                       jnp.bfloat16)}


@pytest.mark.parametrize("case", HANDOFF_ROWS)
def test_handoff_relayout_is_tile_linear(one_chip, case):
    """The hand-off's relayout of one finished row compiles, fits, and
    returns whole (8, 128) tiles of 32-bit words with no sub-word
    packing: a layout whose bytes are in row-major order, so the copy
    to the host needs no untiling."""
    from repro.serving.handoff import tile_linear
    wl, frac, r, m, dtype = HANDOFF_ROWS[case]
    row = _state(one_chip, BlockLayout(frac, r, m), wl)
    text = _compile(tile_linear, _sds(one_chip, row.shape, dtype))
    out = re.search(r"entry_computation_layout=\{\(.*?\)->(.*?)\}",
                    text).group(1)
    assert re.fullmatch(r"u32\[\d+,128\]\{1,0:T\(8,128\)", out), out


def _strip_sizes(frac, r_b, n_shards):
    """The strip decomposition's sizes at block level ``r_b``, from the
    fractal's block count per expanded block row (no per-block table):
    the same balanced row partition, so ``nb_local`` and ``edge_rows``
    are exact, and each routing list at most its shard's first or last
    row."""
    from repro.core.compact import _balanced_contiguous_partition
    slots = np.zeros(frac.s, np.int64)
    for _, y in frac.positions:
        slots[y] += 1
    counts = np.ones(1, np.int64)
    for _ in range(r_b):
        counts = np.kron(slots, counts)
    counts = counts[counts > 0]
    bounds = _balanced_contiguous_partition(counts, n_shards)
    edge = np.array([(counts[a], counts[b - 1]) for a, b in bounds])
    nbl = max(int(counts[a:b].sum()) for a, b in bounds)
    return SimpleNamespace(
        valid=True, nb_local=nbl, nb_padded=n_shards * nbl,
        edge_rows=edge, ms_prev=int(edge[:, 0].max()),
        ms_next=int(edge[:, 1].max()))


def test_strip_sizes_bound_the_decomposition():
    """The sizes the r=20 compile below takes agree with the real
    decomposition where it can be built: nb_local and the edge rows
    exact, the routing widths bounded."""
    frac, n = fractals.SIERPINSKI, 4
    for r in (9, 11):
        layout = BlockLayout(frac, r, 4)
        d = layout.strip_decomposition(n)
        got = _strip_sizes(frac, layout.r_b, n)
        assert got.nb_local == d.nb_local
        np.testing.assert_array_equal(got.edge_rows, d.edge_rows)
        assert d.ms_prev <= got.ms_prev and d.ms_next <= got.ms_next


def test_r20_shard_pass_takes_bounded_chunks(topo):
    """A shard of the r=20 domain (10.77 M blocks) runs each pass in at
    most ``MAX_SHARD_CHUNKS`` chunks, each a whole multiple of
    ``window_chunk``'s, its slots a whole number of them; a shard that
    fits the bound keeps ``window_chunk``'s size."""
    from repro.core import distributed
    from repro.core.compact import window_chunk
    frac = fractals.SIERPINSKI
    mesh = Mesh(np.array(topo.devices), ("data",))
    n = len(topo.devices)
    for r, grows in ((20, True), (12, False)):
        eng = distributed.DistributedSqueezeEngine(
            BlockLayout(frac, r, 4), mesh, workload=LIFE, compute="jnp")
        eng.__dict__["decomp"] = _strip_sizes(frac, r - 4, n)
        base = window_chunk(16, 3)
        chunk = eng._chunk(3)
        assert chunk % base == 0 and (grows or chunk > eng.nb_local)
        assert -(-eng.nb_local // chunk) <= distributed.MAX_SHARD_CHUNKS
        assert (chunk > base) == grows, (r, chunk, base)
        if grows:
            assert eng.nb_local % chunk == 0
            assert eng.nb_local - eng.decomp.nb_local < chunk


#: arguments + temporaries a chip of the r=20 run program took while its
#: chunks gathered sub-word block rows (described v5e:2x2, jax 0.9.0)
R20_ROW_GATHER_BYTES = 3_467_365_376 + 5_664_686_592


def test_sharded_r20_step_program_fits(topo):
    """The service's run program for Life at Sierpinski r=20, m=4 (11.0
    GB of u8 block state, one domain) sharded over the four chips of a
    v5e host, B=1, donated: it compiles for the described ``v5e:2x2``,
    exchanges by collective permutes only, and each chip's arguments +
    output + temporaries - aliased fit its 16 GB."""
    from repro.core.distributed import DistributedSqueezeEngine
    frac, r, m = fractals.SIERPINSKI, 20, 4
    mesh = Mesh(np.array(topo.devices), ("data",))
    eng = DistributedSqueezeEngine(BlockLayout(frac, r, m), mesh,
                                   workload=LIFE, compute="jnp")
    d = _strip_sizes(frac, r - m, len(topo.devices))
    eng.__dict__["decomp"] = d   # the sizes, without the 43 M-block build
    assert eng.exchange_mode == "p2p" and eng.effective_fusion_k == 3
    ns = len(topo.devices)

    def arg(shape, spec, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    row = P("data", None)
    ops = (arg((eng.nb_padded + ns, 9), row), arg((ns, d.ms_prev), row),
           arg((ns, d.ms_next), row), arg((ns, 1), row))
    state = arg((1, eng.nb_padded, 16, 16), P(None, "data"), jnp.uint8)
    compiled = eng.run_program(donate=True).lower(
        state, arg((), P()), ops).compile()
    text = _fits(compiled)
    assert "collective-permute" in text and "all-gather" not in text
    # the chunk gathers read whole 32-bit words (two blocks to a row of
    # the word view), none a quarter of each word
    chunk = eng._chunk(3)
    gathers = re.findall(r"= (\w+)\[(\d+),(\d+)\]\S* gather\(", text)
    assert ("u8", str(chunk), "256") not in gathers
    assert gathers.count(("u32", str(chunk), "128")) >= 9
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used <= 1.01 * R20_ROW_GATHER_BYTES, used


def test_r20_shard_handoff_piece_fits(one_chip):
    """A shard of the r=20 domain goes to the host in pieces of at most
    ``PIECE_BYTES``: the relayout of one piece compiles, fits beside the
    state, and is tile-linear."""
    from repro.serving.handoff import PIECE_BYTES, tile_linear
    n = PIECE_BYTES // (16 * 16)
    text = _compile(tile_linear, _sds(one_chip, (n, 16, 16), jnp.uint8))
    out = re.search(r"entry_computation_layout=\{\(.*?\)->(.*?)\}",
                    text).group(1)
    assert re.fullmatch(r"u32\[\d+,128\]\{1,0:T\(8,128\)", out), out
