"""The sharded path chosen by the program itself: the ``'auto'`` engine
kind's rule, the sharded engine's operands (no per-block window table),
and ``FractalService`` serving Life as ``'dist-block'`` over four
placeholder devices, bit-exact against the plain reference and the
single-device ``'block'`` kind (``_dist_service_check.py``, in a
subprocess so the device count never leaks into this process)."""
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import fractals
from repro.core.compact import BlockLayout
from repro.core.distributed import make_distributed_engine
from repro.core.stencil import SqueezeBlockEngine
from repro.serving import SimRequest
from repro.tuning import spec
from repro.workloads.rules import GRAY_SCOTT, LIFE

REPO = pathlib.Path(__file__).resolve().parents[1]

#: what one v5e chip may report as ``bytes_limit``: between the 15.75 GB
#: its compiler lets a program use and its whole 16 GB of HBM
V5E_LIMITS = (15.75 * 10 ** 9, 16 * 10 ** 9)

#: (fractal, r, m, workload) of the benchmark's configurations
CELLS = {"life-r17": (fractals.SIERPINSKI, 17, 4, LIFE),
         "grayscott-r9": (fractals.CARPET, 9, 2, GRAY_SCOTT),
         "life-r20": (fractals.SIERPINSKI, 20, 4, LIFE)}


def _req(name):
    frac, r, m, wl = CELLS[name]
    return SimRequest(frac=frac, r=r, m=m, steps=32, workload=wl)


@pytest.mark.parametrize("state,limit,devices,want", [
    (1_000, 10_000, 4, "block"),            # fits: never shards
    (3_000, 10_000, 4, "dist-block"),       # 4.25 x 3000 > 10000
    (3_000, 10_000, 1, "block"),            # nothing to shard over
    (3_000, None, 4, "block"),              # no limit reported
    (2_352, 10_000, 2, "block"),            # 9996 fits, just
    (2_353, 10_000, 2, "dist-block"),       # 10000.25 does not
])
def test_auto_kind_rule(state, limit, devices, want):
    assert spec.auto_kind(state, limit, devices) == want


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("limit", V5E_LIMITS)
@pytest.mark.parametrize("name", sorted(CELLS))
def test_benchmark_configurations_resolve(monkeypatch, name, limit, chips):
    """Both one-chip cells stay on ``'block'`` on one v5e chip and on a
    four-chip host; r=20 shards over a host's four chips, and on one
    chip stays ``'block'`` (and does not fit, which the benchmark's
    four-chip cell avoids)."""
    monkeypatch.setattr(spec, "device_capacity", lambda: (limit, chips))
    b = _req(name).bucket
    sharded = name == "life-r20" and chips == 4
    assert b.kind == ("dist-block" if sharded else "block"), b
    assert b.mesh_shape == ((4,) if sharded else None)
    assert b.fusion_k == 3


def test_state_bytes_of_the_benchmark_rows():
    assert _req("life-r17").state_bytes == 3 ** 13 * 256
    assert _req("grayscott-r9").state_bytes == 2 * 8 ** 7 * 81 * 4
    assert _req("life-r20").state_bytes == 3 ** 16 * 256 == 11_019_960_576


def test_explicit_kind_is_honoured(monkeypatch):
    monkeypatch.setattr(spec, "device_capacity", lambda: (1, 4))
    frac, r, m, wl = CELLS["life-r17"]
    req = SimRequest(frac=frac, r=r, m=m, steps=4, workload=wl,
                     kind="pallas-mxu")
    assert req.bucket.kind == "pallas-mxu"


def test_no_window_operand_is_built(monkeypatch):
    """The sharded XLA path builds no (nb, w, w) occupancy table, on the
    host or on the device: ``halo_mask`` is never called, every operand
    is a table of at most 9 columns, and a run still equals the
    single-device engine."""
    def refuse(self, k):
        raise AssertionError("a per-block halo mask was built")

    monkeypatch.setattr(BlockLayout, "halo_mask", refuse)
    layout = BlockLayout(fractals.SIERPINSKI, 6, 2)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    for exchange in ("p2p", "gather"):
        eng = make_distributed_engine(layout, mesh, workload=LIFE,
                                      compute="jnp", fusion_k=3,
                                      exchange=exchange)
        for op in eng.operands():
            assert op.ndim == 2 and op.shape[1] <= 9, op.shape
        out = eng.to_dense(eng.run(eng.init_random(4), 7))
        ref = SqueezeBlockEngine(layout, LIFE, fusion_k=3)
        want = ref.run(ref.init_random(4), 7)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("piece_bytes", [1 << 30, 3000])
def test_host_dense_matches_to_dense(monkeypatch, piece_bytes):
    """The per-shard hand-off, whole and in overlapping pieces, gives
    the compact-order state bit for bit, padding slots left out."""
    from repro.serving import handoff
    monkeypatch.setattr(handoff, "PIECE_BYTES", piece_bytes)
    layout = BlockLayout(fractals.SIERPINSKI, 8, 2)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    eng = make_distributed_engine(layout, mesh, workload=LIFE,
                                  compute="jnp", exchange="p2p")
    state = eng.run(eng.init_random(9), 5)
    got = eng.host_dense(state)
    assert got.shape == (layout.n_blocks, layout.rho, layout.rho)
    np.testing.assert_array_equal(got, np.asarray(eng.to_dense(state)))


def test_service_serves_dist_block_over_four_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO / "src"), str(REPO), env.get("PYTHONPATH", "")])
    out = subprocess.run(
        [sys.executable, str(REPO / "tests" / "_dist_service_check.py")],
        env=env, capture_output=True, text=True, timeout=200)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "DIST_SERVICE_OK" in out.stdout
