"""Sub-word block states gathered as whole 32-bit words
(``compact.WordView``): ``map_chunks`` reads through the word view
exactly what the row gather reads, and the rule picks the view from the
state's dtype and leading dims. Only a caller whose state shows all its
leading dims (the sharded step) asks for the view; a per-row state
under the runner's vmap keeps the row gather."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import fractals
from repro.core.compact import BlockLayout, WordView

#: rho=16 (only ``rho`` is read from it); the states hold
#: more blocks than one span of the view (256 blocks), so both halves of
#: its rows are read
LAYOUT = BlockLayout(fractals.SIERPINSKI, 7, 4)
RHO = LAYOUT.rho


def _cells(shape, dtype, seed):
    x = np.random.default_rng(seed).integers(0, 256, shape)
    if dtype == jnp.bool_:
        x = x % 2
    return jnp.asarray(x).astype(dtype)


def _xor_update(center, nbr, rows):
    """A chunk update that reads the center and every table column."""
    del rows
    return functools.reduce(jnp.bitwise_xor, [center] + [
        jnp.roll(nbr(j), j + 1, axis=-1) for j in range(3)])


def _add_update(center, nbr, rows):
    del rows
    return center + nbr(0)


def _both_views(monkeypatch, fn):
    """``fn()`` through the rule's view, then with the row gather."""
    got = fn()
    with monkeypatch.context() as m:
        m.setattr(WordView, "of", classmethod(lambda cls, *a: None))
        want = fn()
    return got, want


@pytest.mark.parametrize("lead,dtype", [
    ((1,), jnp.uint8), ((2,), jnp.uint8), ((3,), jnp.uint8),
    ((), jnp.bool_), ((1, 1), jnp.bool_)])
@pytest.mark.parametrize("n", [299, 300])
@pytest.mark.parametrize("chunk", [37, 512])
def test_word_view_matches_row_gather(monkeypatch, lead, dtype, n, chunk):
    """Odd and even block counts, ghost ids and ids past the state (zero
    blocks), padding rows of the last chunk, and the one-chunk path."""
    assert WordView.of(lead, dtype, RHO) is not None
    state = _cells(lead + (n, RHO, RHO), dtype, n)
    table = jnp.asarray(np.random.default_rng(1).integers(
        0, n + 4, (n, 3)), jnp.int32).at[0].set(n)   # the ghost sentinel

    def run():
        return np.asarray(jax.jit(lambda s, t: LAYOUT.map_chunks(
            s, t, chunk, _xor_update, source=LAYOUT.chunk_source(s)))(
                state, table))

    got, want = _both_views(monkeypatch, run)
    assert got.dtype == want.dtype == np.dtype(dtype)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("chunk", [16, 64])
def test_word_view_reads_ids_and_received_rows(monkeypatch, chunk):
    """The p2p boundary pass: ``ids`` name the updated blocks, ids past
    the shard name rows of ``extra`` (the zero row, then the received
    ones), and ids past both read as zero."""
    n, m, start, width = 300, 5, 250, 40
    state = _cells((1, 1, n, RHO, RHO), jnp.uint8, 2)
    extra = _cells((1, 1, m, RHO * RHO), jnp.uint8, 3).at[..., 0, :].set(0)
    table = jnp.asarray(np.random.default_rng(4).integers(
        0, n + m + 3, (width, 3)), jnp.int32)
    ids = start + jnp.arange(width, dtype=jnp.int32)

    def run():
        return np.asarray(jax.jit(lambda s, t, i, e: LAYOUT.map_chunks(
            s, t, chunk, _xor_update, ids=i, extra=e,
            source=LAYOUT.chunk_source(s)))(state, table, ids, extra))

    got, want = _both_views(monkeypatch, run)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("lead,dtype,rho,group", [
    ((), jnp.uint8, 16, 2), ((1,), jnp.uint8, 16, 2),
    ((1, 1), jnp.uint8, 16, 2), ((3,), jnp.uint8, 16, 2),
    ((), jnp.bool_, 16, 2), ((1,), jnp.bfloat16, 16, 1),
    ((), jnp.uint8, 32, 1), ((), jnp.uint8, 8, 8),
    ((4,), jnp.uint8, 16, None), ((2,), jnp.bfloat16, 16, None),
    ((2, 2), jnp.float32, 9, None), ((1,), jnp.float32, 16, None),
    ((), jnp.uint8, 9, None), ((), jnp.uint8, 4, None)])
def test_view_rule(lead, dtype, rho, group):
    """Words where a cell's leading dims span under 4 bytes and a block
    is whole sublane tiles of words that tile 128 lanes; rows else."""
    view = WordView.of(lead, dtype, rho)
    assert (None if view is None else view.group) == group


@pytest.mark.parametrize("lead,dtype,rho,n", [
    ((), jnp.uint8, 16, 27), ((1, 1), jnp.uint8, 16, 600),
    ((3,), jnp.uint8, 16, 3000), ((1,), jnp.bfloat16, 16, 300),
    ((), jnp.uint8, 32, 130), ((), jnp.uint8, 8, 2000)])
def test_kernels_match_reference(lead, dtype, rho, n):
    """The Pallas packing and unpacking kernels (interpreted) agree with
    the references, and every block reads back as its cells; a code of
    -1 reads as zero."""
    view = WordView.of(lead, dtype, rho)
    cells = _cells(lead + (n, rho * rho), dtype, n)
    bits = jax.lax.bitcast_convert_type(cells, view._uint)
    ref = view.pack_reference(bits)
    got = view.pack_kernel(bits, interpret=True)
    assert got.shape == ref.shape
    ids = jnp.asarray(np.random.default_rng(n).permutation(n), jnp.int32)
    rows, sub = view._rows(ref, ids)
    np.testing.assert_array_equal(view._rows(got, ids)[0], rows)
    code = sub.at[::5].set(-1)
    want = view.unpack_reference(rows, code)
    np.testing.assert_array_equal(
        view.unpack_kernel(rows, code, interpret=True), want)
    kept = np.asarray(code) >= 0
    np.testing.assert_array_equal(np.asarray(want)[..., ~kept, :], 0)
    np.testing.assert_array_equal(
        np.asarray(want)[..., kept, :],
        np.asarray(bits)[..., np.asarray(ids)[kept], :])


def _gather_views(fn, *args):
    with obs.enabled_scope():
        obs.reset()
        jitted = jax.jit(fn)
        jitted(*args)
        jitted(*args)   # cached: no second trace
        return {c["labels"]["view"]: c["value"] for c in
                obs.default_registry().snapshot()["counters"]
                if c["name"] == "engine.gather_view" and c["value"]}


@pytest.mark.parametrize("lead,dtype,asked,view", [
    ((1,), jnp.uint8, True, "words"), ((4,), jnp.uint8, True, "rows"),
    ((1, 2), jnp.float32, True, "rows"), ((1,), jnp.uint8, False, "rows")])
def test_gather_view_counted_once_per_trace(lead, dtype, asked, view):
    """The view a trace takes is counted once; without a ``source`` the
    row gather is taken whatever the state."""
    n = 12
    state = _cells(lead + (n, RHO, RHO), dtype, 5)
    table = jnp.zeros((n, 3), jnp.int32)
    assert _gather_views(lambda s, t: LAYOUT.map_chunks(
        s, t, 4, _add_update,
        source=LAYOUT.chunk_source(s) if asked else None),
        state, table) == {view: 1}


#: the batch sizes the benchmark warms for its one-chip Life cell
#: (``chipbench.loadgen.warm_sizes`` of four clients)
ONE_CHIP_BATCHES = (1, 2, 3, 4)


@pytest.mark.parametrize("rows", ONE_CHIP_BATCHES)
def test_runner_batch_keeps_row_gather_and_matches(rows):
    """The runner maps the one-chip block engine over its batch: every
    batch of u8 Life states keeps the row gather, and each row ends
    where the row alone ends."""
    from repro.workloads.runner import BatchedRunner
    from repro.workloads.rules import LIFE
    frac, r, m = fractals.SIERPINSKI, 10, 4
    runner = BatchedRunner()
    states = runner.init_batch("block", frac, r, seeds=range(rows), m=m,
                               workload=LIFE)
    jax.clear_caches()   # the engine's step is traced anew for this batch
    with obs.enabled_scope():
        obs.reset()
        out = runner.run("block", frac, r, states=states, steps=7, m=m,
                         workload=LIFE)
        counted = {c["labels"]["view"] for c in
                   obs.default_registry().snapshot()["counters"]
                   if c["name"] == "engine.gather_view" and c["value"]}
    assert counted == {"rows"}
    alone = BatchedRunner()
    for i in range(rows):
        one = alone.run("block", frac, r, states=states[i:i + 1], steps=7,
                        m=m, workload=LIFE)
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(one[0]))


@pytest.mark.parametrize("seeds,view", [
    ((3,), "words"), ((3, 4, 5), "words"), ((3, 4, 5, 6), "rows")])
def test_sharded_step_takes_words_and_matches(seeds, view):
    """The sharded p2p step (one-device mesh) asks for the view of its
    (B, C, n) state: under four u8 rows it gathers words, and every row
    ends bit-equal to the single-device block engine's."""
    from repro.core.distributed import make_distributed_engine
    from repro.core.stencil import SqueezeBlockEngine
    from repro.workloads.rules import LIFE
    layout, steps = BlockLayout(fractals.SIERPINSKI, 8, 4), 5
    dist = make_distributed_engine(layout, workload=LIFE, compute="jnp",
                                   fusion_k=2, exchange="p2p")
    with obs.enabled_scope():
        obs.reset()
        out = dist.run(dist.init_batch(list(seeds)), steps)
        counted = {c["labels"]["view"] for c in
                   obs.default_registry().snapshot()["counters"]
                   if c["name"] == "engine.gather_view" and c["value"]}
    assert counted == {view}
    dense = np.asarray(dist.to_dense(out))
    for i, seed in enumerate(seeds):
        eng = SqueezeBlockEngine(layout, LIFE, fusion_k=1)
        s = eng.init_random(seed)
        for _ in range(steps):
            s = eng.step(s)
        np.testing.assert_array_equal(dense[i], np.asarray(s))
