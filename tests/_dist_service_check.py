"""Subprocess body of tests/test_dist_service.py: four placeholder host
devices (the flag must precede any jax import).

A device capacity small enough that one Life row at r=9, m=4 does not
fit makes the ``'auto'`` kind itself pick ``'dist-block'`` over all four
devices. ``FractalService`` then serves two 7-step requests (k=3, so a
remainder launch of depth 1 after the snapshot at step 5, and one of
depth 2 before it) with snapshots, and every returned state equals the
plain reference (``chipbench.reference``) and single-device ``'block'``
bit for bit. The run compiles one program per batch shape, ahead of
its launch, and counts ceil(steps/k) exchanges per segment."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench.reference import domain, rule_for  # noqa: E402
from repro import obs  # noqa: E402
from repro.core.fractals import SIERPINSKI  # noqa: E402
from repro.serving import FractalService, ServiceConfig, SimRequest  # noqa
from repro.tuning import spec as spec_mod  # noqa: E402
from repro.workloads.rules import LIFE  # noqa: E402
from repro.workloads.runner import BatchedRunner  # noqa: E402

R, M, STEPS, SNAP, K = 9, 4, 7, 5, 3
CONFIG = {"fractal": {"name": "sierpinski", "s": 2,
                      "positions": [[0, 0], [0, 1], [1, 1]]},
          "workload": {"name": "life", "rule": "life", "born": [3],
                       "survive": [2, 3]}, "m": M}


def requests(kind, seeds):
    return [SimRequest(frac=SIERPINSKI, r=R, m=M, steps=STEPS,
                       workload=LIFE, kind=kind, k=K, seed=s,
                       snapshot_every=SNAP, rid=f"{kind}-{s}")
            for s in seeds]


def main():
    assert jax.device_count() == 4, jax.devices()
    seeds = [3, 2 ** 31 - 2]
    row = requests("auto", seeds[:1])[0].state_bytes
    # one row needs BLOCK_ROW_WORKING_SET x its state: leave it less
    spec_mod.device_capacity = lambda: (2 * row, jax.local_device_count())
    reqs = requests("auto", seeds)
    bucket = reqs[0].bucket
    assert bucket.kind == "dist-block", bucket
    assert bucket.mesh_shape == (4,), bucket

    runner = BatchedRunner()
    obs.enable()
    obs.reset()
    res = FractalService(ServiceConfig(max_batch=2, hang_threshold_s=60.0),
                         runner=runner).serve(reqs)
    assert [r.status for r in res] == ["ok", "ok"], [
        (r.status, r.error) for r in res]
    eng = runner.engine_for(bucket, frac=SIERPINSKI, workload=LIFE)
    assert eng.exchange_mode == "p2p" and eng.n_shards == 4
    st = eng.exchange_stats()
    assert st.steps == STEPS and st.collectives == 3, st  # 5 = 3+2, then 2
    live = [obs.default_registry().value("dist.shard_live_cells",
                                         shard=str(s)) for s in range(4)]
    assert sum(live) == 3 ** R, live
    obs.disable()

    # warm: another step count on the same batch shape compiles nothing
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    again = [SimRequest(frac=SIERPINSKI, r=R, m=M, steps=4, workload=LIFE,
                        k=K, seed=s, rid=f"again-{s}") for s in seeds]
    out = FractalService(ServiceConfig(max_batch=2, hang_threshold_s=60.0),
                         runner=runner).serve(again)
    assert [r.status for r in out] == ["ok", "ok"]
    assert not compiles, compiles

    want = FractalService(ServiceConfig(max_batch=2, hang_threshold_s=60.0),
                          runner=BatchedRunner()).serve(
        requests("block", seeds))
    plan = domain.plan_tiles(domain.Fractal.from_config(CONFIG), R, M,
                             STEPS)
    rule = rule_for(CONFIG)
    for got, ref, seed in zip(res, want, seeds):
        assert [s for s, _ in got.snapshots] == [SNAP]
        states = dict(got.snapshots)
        states[STEPS] = got.state
        assert got.state.shape == (3 ** (R - M), 16, 16), got.state.shape
        np.testing.assert_array_equal(got.state, ref.state)
        np.testing.assert_array_equal(states[SNAP], dict(ref.snapshots)[SNAP])
        bad = domain.compare_states(plan, rule, seed, states)
        assert bad == 0, f"seed {seed}: {bad} cells differ"
        assert np.count_nonzero(got.state), "the field died out"
    chunked_shards_match()
    print("DIST_SERVICE_OK")


def chunked_shards_match():
    """With chunks of a few blocks, every pass of the sharded XLA step
    (the interior pass, both edge ranges, the padded shard) runs its
    chunk loop; four shards still equal one device bit for bit (Life)
    and to rounding (Gray-Scott, two channels). Again with at most four
    chunks a pass (``MAX_SHARD_CHUNKS``), so that a chunk is a multiple
    of ``window_chunk``'s."""
    from jax.sharding import Mesh

    from repro.core import compact, distributed
    from repro.core.compact import BlockLayout
    from repro.core.distributed import make_distributed_engine
    from repro.core.stencil import SqueezeBlockEngine
    from repro.workloads.rules import GRAY_SCOTT
    chunk_bytes = compact.CHUNK_WINDOW_BYTES
    compact.CHUNK_WINDOW_BYTES = 3 * 16 * 128 * 4   # 3 Life blocks (rho=4)
    cap = distributed.MAX_SHARD_CHUNKS
    try:
        mesh = Mesh(np.array(jax.devices()), ("data",))
        # at the default cap a chunk is window_chunk's (3 Life blocks);
        # at 4 chunks a pass, a larger whole multiple of it
        for max_chunks in (cap, 4):
            distributed.MAX_SHARD_CHUNKS = max_chunks
            for frac, r, m, wl in ((SIERPINSKI, 8, 2, LIFE),
                                   (SIERPINSKI, 7, 2, GRAY_SCOTT)):
                layout = BlockLayout(frac, r, m)
                eng = make_distributed_engine(layout, mesh, workload=wl,
                                              compute="jnp", fusion_k=3)
                chunk = eng._chunk(3)
                base = compact.window_chunk(layout.rho, 3, wl.n_channels)
                assert eng.decomp.nb_local > chunk, (eng.decomp.nb_local,
                                                     chunk)
                assert chunk % base == 0 and eng.nb_local % chunk == 0
                assert eng.nb_local // chunk <= max_chunks
                head, tail = eng.edge_widths
                if max_chunks == cap:
                    assert chunk == base
                    assert min(head, tail) > chunk, (head, tail, chunk)
                else:
                    assert chunk > base
                got = eng.host_dense(eng.run(eng.init_batch([6, 7]),
                                             8)[1])
                ref = SqueezeBlockEngine(layout, wl, fusion_k=3)
                want = np.asarray(ref.run(ref.init_random(7), 8))
                if wl is LIFE:
                    np.testing.assert_array_equal(got, want)
                else:
                    np.testing.assert_allclose(got, want, rtol=1e-5,
                                               atol=1e-5)
                print(f"chunked {wl.name} r={r}: chunk={chunk} "
                      f"nb_local={eng.nb_local} edges={head},{tail} ok")
    finally:
        compact.CHUNK_WINDOW_BYTES = chunk_bytes
        distributed.MAX_SHARD_CHUNKS = cap


if __name__ == "__main__":
    main()
