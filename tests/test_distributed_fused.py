"""In-process coverage of the k-fused distributed engine (main coverage;
the 8-device subprocess check in test_distributed_stencil.py stays as the
multi-device smoke test).

These run on a 1-device mesh — shard_map, the strip all-gather, the
padded-table gathers and every shard-local compute backend execute
exactly as on a real mesh (the collective degenerates), so the full
parity matrix (workload x k x kind), the exchange accounting and the
donation path are all exercised in-process where failures are debuggable.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fractals
from repro.core.compact import BlockLayout
from repro.core.distributed import make_distributed_engine
from repro.core.stencil import SqueezeBlockEngine, make_engine
from repro.workloads.rules import GRAY_SCOTT, HEAT, HIGHLIFE, LIFE
from repro.workloads.runner import BatchedRunner

FRAC, R, M = fractals.SIERPINSKI, 5, 2
WORKLOADS = (LIFE, HIGHLIFE, HEAT, GRAY_SCOTT)
COMPUTES = ("jnp", "fused", "mxu")


def _layout():
    return BlockLayout(FRAC, R, M)


def _reference(layout, wl, seed, steps):
    eng = SqueezeBlockEngine(layout, wl, fusion_k=1)
    s = eng.init_random(seed)
    for _ in range(steps):
        s = eng.step(s)
    return np.asarray(s)


def _assert_state_eq(wl, got, want, msg):
    if jnp.issubdtype(jnp.dtype(wl.dtype), jnp.integer):
        np.testing.assert_array_equal(got, want, err_msg=msg)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=msg)


# ----------------------------------------------------------- strip geometry
@pytest.mark.parametrize("k", [1, 2, 4])
def test_edge_strips_reconstruct_gather_halo_k(k):
    """pack_edge_strips + halo_from_strips_k == the fused kernels' direct
    depth-k strip gather, for every piece (the exchange ships exactly the
    bytes the kernels read)."""
    from repro.kernels.squeeze_stencil import _gather_halo_k
    layout = _layout()
    layout.materialize()
    key = jax.random.PRNGKey(0)
    s = jax.random.randint(key, (1, layout.n_blocks, layout.rho,
                                 layout.rho), 0, 255, jnp.int32)
    strips = layout.pack_edge_strips(s, k)
    strips = jnp.concatenate(
        [strips, jnp.zeros((1, 1) + strips.shape[2:], strips.dtype)],
        axis=1)
    table = jnp.asarray(layout.offset_table(k))
    table = jnp.where(table == layout.ghost, layout.n_blocks, table)
    got = layout.halo_from_strips_k(strips, table, k)
    want = _gather_halo_k(layout, s, k)
    for name, g, w in zip(("top", "bot", "west", "east"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=f"piece {name} k={k}")


def test_edge_strips_bounds():
    layout = _layout()
    state = jnp.zeros((1, layout.n_blocks, layout.rho, layout.rho),
                      jnp.uint8)
    with pytest.raises(ValueError):
        layout.pack_edge_strips(state, 0)
    with pytest.raises(ValueError):
        layout.pack_edge_strips(state, layout.rho + 1)


# ------------------------------------------------------------ parity matrix
@pytest.mark.parametrize("wl", WORKLOADS, ids=lambda w: w.name)
@pytest.mark.parametrize("compute", COMPUTES)
@pytest.mark.parametrize("k", [1, 2, 4])
def test_distributed_parity(wl, compute, k):
    """workload x compute x k parity vs the single-device block engine:
    CA bit-exact, PDE workloads allclose; padding blocks stay dead."""
    layout = _layout()
    steps = 5
    dist = make_distributed_engine(layout, workload=wl, compute=compute,
                                   fusion_k=k, interpret=True)
    out = dist.run(dist.init_random(7), steps)
    want = _reference(layout, wl, 7, steps)
    _assert_state_eq(wl, np.asarray(dist.to_dense(out)), want,
                     f"{wl.name}/{compute}/k={k}")
    pad = np.asarray(out)[..., layout.n_blocks:, :, :]
    assert (pad == 0).all(), "padding blocks came alive"


@pytest.mark.parametrize("compute", COMPUTES)
def test_distributed_batched_parity(compute):
    """B independent simulations through one engine match per-seed
    single-device runs (native batched strip exchange)."""
    layout = _layout()
    seeds, steps = [1, 2, 3], 4
    dist = make_distributed_engine(layout, workload=LIFE, compute=compute,
                                   fusion_k=2, interpret=True)
    out = dist.run(dist.init_batch(seeds), steps)
    dense = np.asarray(dist.to_dense(out))
    for i, seed in enumerate(seeds):
        np.testing.assert_array_equal(
            dense[i], _reference(layout, LIFE, seed, steps),
            err_msg=f"batch element {i} (seed {seed}) {compute}")


def test_multi_channel_batched():
    """Gray-Scott (C=2) with a batch axis: (B, C, nb, rho, rho)."""
    layout = _layout()
    dist = make_distributed_engine(layout, workload=GRAY_SCOTT,
                                   compute="jnp", fusion_k=2,
                                   interpret=True)
    out = dist.run(dist.init_batch([5, 6]), 3)
    assert out.shape[:2] == (2, 2)
    for i, seed in enumerate([5, 6]):
        _assert_state_eq(GRAY_SCOTT, np.asarray(dist.to_dense(out))[i],
                         _reference(layout, GRAY_SCOTT, seed, 3),
                         f"gs batch {i}")


# -------------------------------------------------------- exchange accounting
@pytest.mark.parametrize("exchange", ["gather", "p2p"])
@pytest.mark.parametrize("steps,k", [(5, 2), (6, 3), (7, 4), (4, 1), (3, 4)])
def test_exactly_ceil_steps_over_k_collectives(steps, k, exchange):
    """A run of ``steps`` at fusion depth ``k`` issues exactly
    ceil(steps/k) halo exchanges — the fused remainder launch included
    (NOT floor(steps/k) + (steps % k) single steps) — in BOTH exchange
    modes."""
    layout = _layout()
    dist = make_distributed_engine(layout, workload=LIFE, compute="jnp",
                                   fusion_k=k, interpret=True,
                                   exchange=exchange)
    dist.run(dist.init_random(0), steps)
    st = dist.exchange_stats()
    assert st.steps == steps
    assert st.collectives == math.ceil(steps / k), st
    if exchange == "gather":
        assert st.exchanged_bytes > 0
    else:
        # exact wire model; zero on this 1-shard mesh (nothing crosses
        # a device boundary — the permutes carry (n_shards-1) payloads)
        assert st.exchanged_bytes == (math.ceil(steps / k)
                                      * dist.permute_bytes(k))
    dist.reset_exchange_stats()
    assert dist.exchange_stats().collectives == 0


def test_one_all_gather_in_lowered_step():
    """Structural check behind the counters: the lowered fused gather
    step contains exactly ONE all_gather op (strips only, once per
    launch)."""
    layout = _layout()
    dist = make_distributed_engine(layout, workload=LIFE, compute="jnp",
                                   fusion_k=2, interpret=True,
                                   exchange="gather")
    txt = dist.lowered_step_text(dist.init_random(0), 2)
    assert txt.count('"stablehlo.all_gather"') == 1, txt[:2000]


def test_p2p_lowered_step_structure():
    """The p2p twin: two neighbor collective_permutes (forward and
    backward shift), NO all_gather anywhere in the lowered launch —
    neighbor-only exchange is structural, not just accounted."""
    layout = _layout()
    dist = make_distributed_engine(layout, workload=LIFE, compute="jnp",
                                   fusion_k=2, interpret=True,
                                   exchange="p2p")
    assert dist.exchange_mode == "p2p"
    txt = dist.lowered_step_text(dist.init_random(0), 2)
    assert txt.count('"stablehlo.all_gather"') == 0, txt[:2000]
    assert txt.count('"stablehlo.collective_permute"') == 2, txt[:2000]


def test_exchange_bytes_model():
    """bytes_gathered matches the analytic strip volume: one depth-k
    gather ships 4*k*rho cells per block (vs 4*(rho+2) per step per block
    for k=1 stepping — per step, fusion trades k collectives for one)."""
    layout = _layout()
    k = 3
    dist = make_distributed_engine(layout, workload=LIFE, compute="jnp",
                                   fusion_k=k, interpret=True,
                                   exchange="gather")
    dist.run(dist.init_random(0), k)  # one fused launch
    st = dist.exchange_stats()
    assert st.collectives == 1
    assert st.bytes_gathered == dist.strip_bytes(k)
    assert st.bytes_permuted == 0 and st.neighbor_sends == 0
    assert dist.strip_bytes(k) == (dist.nb_padded * 4 * k * layout.rho
                                   * jnp.dtype(LIFE.dtype).itemsize)


def test_exchange_bytes_model_p2p():
    """The p2p twin: bytes_permuted matches the analytic per-neighbor
    routing volume (ms_prev + ms_next slots per device per launch: whole
    blocks on the XLA compute, depth-k edge strips on the kernel
    computes), neighbor_sends counts 2*(n_shards-1) directed sends per
    launch, and nothing is all-gathered."""
    layout = _layout()
    k = 3
    dist = make_distributed_engine(layout, workload=LIFE, compute="jnp",
                                   fusion_k=k, interpret=True,
                                   exchange="p2p")
    dist.run(dist.init_random(0), k)  # one fused launch
    st = dist.exchange_stats()
    assert st.collectives == 1
    assert st.bytes_gathered == 0
    assert st.bytes_permuted == dist.permute_bytes(k)
    assert st.neighbor_sends == 2 * (dist.n_shards - 1)
    d = dist.decomp
    itemsize = jnp.dtype(LIFE.dtype).itemsize
    assert dist.wire_bytes_per_device(k) == (
        (d.ms_prev + d.ms_next) * layout.rho ** 2 * itemsize)
    kernel = make_distributed_engine(layout, workload=LIFE,
                                     compute="fused", fusion_k=k,
                                     interpret=True, exchange="p2p")
    assert kernel.wire_bytes_per_device(k) == (
        (d.ms_prev + d.ms_next) * 4 * k * layout.rho * itemsize)


def test_memory_bytes():
    layout = _layout()
    dist = make_distributed_engine(layout, workload=GRAY_SCOTT,
                                   interpret=True)
    assert dist.memory_bytes() == (2 * dist.nb_padded * layout.rho ** 2
                                   * 4)  # C=2, f32


# ------------------------------------------------------------------ donation
def test_run_donate_parity():
    layout = _layout()
    dist = make_distributed_engine(layout, workload=LIFE, compute="jnp",
                                   fusion_k=2, interpret=True)
    s = dist.init_random(11)
    want = np.asarray(dist.to_dense(dist.run(s, 5)))
    s2 = dist.init_random(11)
    got = np.asarray(dist.to_dense(dist.run(s2, 5, donate=True)))
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------------- errors
def test_fusion_k_bounds():
    layout = _layout()
    with pytest.raises(ValueError):
        make_distributed_engine(layout, fusion_k=0)
    with pytest.raises(ValueError):
        make_distributed_engine(layout, fusion_k=layout.rho + 1)
    dist = make_distributed_engine(layout, interpret=True)
    with pytest.raises(ValueError):
        dist.step_k(dist.init_random(0), layout.rho + 1)
    with pytest.raises(ValueError):
        make_distributed_engine(layout, compute="vpu")


# ----------------------------------------------------------- engine registry
def test_make_engine_dist_kinds():
    eng = make_engine("dist-mxu", FRAC, R, M, workload=HEAT, fusion_k=2)
    assert eng.compute == "mxu" and eng.workload is HEAT
    assert eng.effective_fusion_k == 2
    eng = make_engine("dist-block", FRAC, R, M)
    assert eng.compute == "jnp"
    eng = make_engine("dist-fused", FRAC, R, M)
    assert eng.compute == "fused"


# ------------------------------------------------------------------- runner
def test_runner_dist_kind_parity_and_cache():
    runner = BatchedRunner()
    mesh = jax.sharding.Mesh(jax.devices(), ("data",))
    seeds, steps = [4, 9], 5
    states = runner.init_batch("dist-block", FRAC, R, seeds, m=M,
                               workload=LIFE, mesh=mesh)
    out = runner.run("dist-block", FRAC, R, states, steps, m=M,
                     workload=LIFE, k=2, mesh=mesh)
    layout = _layout()
    eng = runner.engine_for("dist-block", FRAC, R, M, LIFE, k=2, mesh=mesh)
    dense = np.asarray(eng.to_dense(out))
    for i, seed in enumerate(seeds):
        np.testing.assert_array_equal(
            dense[i], _reference(layout, LIFE, seed, steps))
    # ceil(steps/k) collectives through the runner path too
    assert eng.exchange_stats().collectives == math.ceil(steps / 2)
    # one cached engine per (kind, ..., k, mesh); same config hits cache
    builds = runner.stats.builds
    runner.engine_for("dist-block", FRAC, R, M, LIFE, k=2, mesh=mesh)
    assert runner.stats.builds == builds


def test_runner_batch_placement_regular_kind():
    """Non-dist kinds with a mesh shard the BATCH axis (whole sims per
    device) — run still matches the meshless path."""
    runner = BatchedRunner()
    mesh = jax.sharding.Mesh(jax.devices(), ("data",))
    states = runner.init_batch("block", FRAC, R, [1, 2], m=M,
                               workload=LIFE, mesh=mesh)
    out = runner.run("block", FRAC, R, states, 3, m=M, workload=LIFE, k=2)
    layout = _layout()
    for i, seed in enumerate([1, 2]):
        np.testing.assert_array_equal(
            np.asarray(out)[i], _reference(layout, LIFE, seed, 3))


def test_runner_to_expanded_dist():
    runner = BatchedRunner()
    mesh = jax.sharding.Mesh(jax.devices(), ("data",))
    states = runner.init_batch("dist-block", FRAC, R, [0], m=M,
                               workload=LIFE, mesh=mesh)
    exp = runner.to_expanded("dist-block", FRAC, R, states, m=M,
                             workload=LIFE, mesh=mesh)
    n = FRAC.side(R)
    assert exp.shape == (1, n, n)
