"""jit'd public wrappers for the Pallas kernels.

``interpret=None`` everywhere means auto-detect: compiled Mosaic on a TPU
runtime, the Pallas interpreter on the CPU (the test suite's backend),
an error on any other backend. Pass an explicit bool to pin it.
Resolution happens once, in the kernel entry points
(``kernels.common.resolve_interpret``); these wrappers pass
``interpret`` through untouched.

With telemetry enabled, every stencil entry point counts
``kernel.entry{op=...}`` on the registry. The wrapper body runs once
per *Python-level* entry: eagerly that is one count per kernel
dispatch; inside a jit (the engines' cached run loops) it runs only
while tracing — so a growing ``kernel.entry`` under a cached jit is a
retrace detector, the same discipline as ``engine.trace`` (DESIGN.md
Section 7).
"""
from __future__ import annotations

from typing import Optional

from repro import obs
from repro.core.compact import BlockLayout
from repro.core.fractals import NBBFractal
from repro.kernels.common import default_interpret  # noqa: F401  re-export
from repro.workloads.rules import LIFE
from repro.kernels import attention as _attention
from repro.kernels import lambda_map as _lambda
from repro.kernels import nu_map as _nu
from repro.kernels import squeeze_stencil as _stencil


def nu_map_tc(frac: NBBFractal, r: int, ex, ey, *,
              interpret: Optional[bool] = None):
    """Tensor-core (MXU) nu(w): (cx, cy, valid)."""
    return _nu.nu_map_pallas(frac, r, ex, ey, interpret=interpret)


def lambda_map_tc(frac: NBBFractal, r: int, cx, cy, *,
                  interpret: Optional[bool] = None):
    """Tensor-core (MXU) lambda(w): (ex, ey)."""
    return _lambda.lambda_map_pallas(frac, r, cx, cy, interpret=interpret)


def stencil_step_blocks(layout: BlockLayout, state, workload=LIFE, *,
                        interpret: Optional[bool] = None, table=None):
    """Fused block-level workload step, v1 (neighbor-block staging)."""
    obs.inc("kernel.entry", op="stencil_step_blocks")
    return _stencil.stencil_step_blocks(layout, state, workload,
                                        interpret=interpret, table=table)


def stencil_step_strips(layout: BlockLayout, state, workload=LIFE, *,
                        interpret: Optional[bool] = None, table=None):
    """Fused block-level workload step, v2 (strip halos)."""
    obs.inc("kernel.entry", op="stencil_step_strips")
    return _stencil.stencil_step_strips(layout, state, workload,
                                        interpret=interpret, table=table)


def stencil_step_fused(layout: BlockLayout, state, workload=LIFE, *,
                       interpret: Optional[bool] = None, table=None):
    """Fused block-level workload step, v3 (in-kernel strip reads)."""
    obs.inc("kernel.entry", op="stencil_step_fused")
    return _stencil.stencil_step_fused(layout, state, workload,
                                       interpret=interpret, table=table)


def stencil_step_fused_k(layout: BlockLayout, state, workload=LIFE, *,
                         k: int = 2, interpret: Optional[bool] = None,
                         table=None):
    """Fused block-level workload step, v4 (temporal fusion): k exact
    steps per launch on a depth-k halo tile held in VMEM. k <= rho."""
    obs.inc("kernel.entry", op="stencil_step_fused_k")
    return _stencil.stencil_step_fused_k(layout, state, workload, k=k,
                                         interpret=interpret, table=table)


def stencil_step_mxu(layout: BlockLayout, state, workload=LIFE, *,
                     p: Optional[int] = None,
                     interpret: Optional[bool] = None, table=None):
    """Fused block-level workload step, v5 (MXU stencil-as-matmul on
    lane-packed macro-tiles). ``p`` overrides the macro-tile packing
    (blocks per macro-tile; None = lane heuristic — the autotuner
    persists per-config winners)."""
    obs.inc("kernel.entry", op="stencil_step_mxu")
    return _stencil.stencil_step_mxu(layout, state, workload, p=p,
                                     interpret=interpret, table=table)


def stencil_step_mxu_k(layout: BlockLayout, state, workload=LIFE, *,
                       k: int = 2, p: Optional[int] = None,
                       interpret: Optional[bool] = None, table=None):
    """Fused block-level workload step, v5 temporal fusion: k exact steps
    per MXU macro-tile launch (k <= rho). ``p`` overrides the macro-tile
    packing (None = lane heuristic)."""
    obs.inc("kernel.entry", op="stencil_step_mxu_k")
    return _stencil.stencil_step_mxu_k(layout, state, workload, k=k, p=p,
                                       interpret=interpret, table=table)


def stencil_step_mxu_batched(layout: BlockLayout, states, workload=LIFE, *,
                             k: int = 1, p: Optional[int] = None,
                             interpret: Optional[bool] = None, table=None):
    """v5 native batch grid: B simulations x k exact steps in one kernel
    dispatch over (B, n_macro_tiles); states (B, C?, n_blocks, rho, rho).
    ``p`` overrides the macro-tile packing (None = lane heuristic)."""
    obs.inc("kernel.entry", op="stencil_step_mxu_batched")
    return _stencil.stencil_step_mxu_batched(layout, states, workload, k=k,
                                             p=p, interpret=interpret,
                                             table=table)


def stencil3d_step_fused_k(layout, state, workload=None, *, k: int = 2,
                           interpret: Optional[bool] = None):
    """Fused 3D block-level workload step (v4-style temporal fusion):
    k exact steps per launch on a depth-k (rho+2k)^3 window in VMEM.
    ``layout`` is a ``compact3d.BlockLayout3D``; k <= rho."""
    obs.inc("kernel.entry", op="stencil3d_step_fused_k")
    from repro.kernels import squeeze_stencil3d as _s3
    from repro.workloads.rules import LIFE3D
    return _s3.stencil3d_step_fused_k(
        layout, state, LIFE3D if workload is None else workload, k=k,
        interpret=interpret)


def stencil3d_step_mxu_k(layout, state, workload=None, *, k: int = 1,
                         p: Optional[int] = None,
                         interpret: Optional[bool] = None):
    """Fused 3D block-level workload step (v5-style MXU): the 26-cell
    aggregation as banded matmuls per z-slab on lane-packed macro-tiles.
    ``layout`` is a ``compact3d.BlockLayout3D``; k <= rho. ``p``
    overrides the macro-tile packing (None = lane heuristic)."""
    obs.inc("kernel.entry", op="stencil3d_step_mxu_k")
    from repro.kernels import squeeze_stencil3d as _s3
    from repro.workloads.rules import LIFE3D
    return _s3.stencil3d_step_mxu_k(
        layout, state, LIFE3D if workload is None else workload, k=k, p=p,
        interpret=interpret)


def life_step_blocks(layout: BlockLayout, state, *,
                     interpret: Optional[bool] = None):
    """Fused block-level GoL step, v1 (neighbor-block staging)."""
    obs.inc("kernel.entry", op="life_step_blocks")
    return _stencil.life_step_blocks(layout, state, interpret=interpret)


def life_step_strips(layout: BlockLayout, state, *,
                     interpret: Optional[bool] = None):
    """Fused block-level GoL step, v2 (strip halos; lower HBM traffic)."""
    obs.inc("kernel.entry", op="life_step_strips")
    return _stencil.life_step_strips(layout, state, interpret=interpret)


def life_step_fused(layout: BlockLayout, state, *,
                    interpret: Optional[bool] = None):
    """Fused block-level GoL step, v3 (in-kernel strip reads; no halo
    tensor materialised)."""
    obs.inc("kernel.entry", op="life_step_fused")
    return _stencil.life_step_fused(layout, state, interpret=interpret)


def ssd_chunk_scan(x, dt, a, bm, cm, *, chunk: int = 128,
                   interpret: Optional[bool] = None):
    """Mamba-2 SSD scan with the Pallas intra-chunk kernel."""
    from repro.kernels import ssd_chunk as _ssd
    return _ssd.ssd_chunk_scan(x, dt, a, bm, cm, chunk=chunk,
                               interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None):
    """Blocked online-softmax attention (causal/window/softcap)."""
    return _attention.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, interpret=interpret)


__all__ = ["nu_map_tc", "lambda_map_tc", "life_step_blocks",
           "life_step_strips", "life_step_fused", "stencil_step_blocks",
           "stencil_step_strips", "stencil_step_fused",
           "stencil_step_fused_k", "stencil_step_mxu",
           "stencil_step_mxu_k", "stencil_step_mxu_batched",
           "stencil3d_step_fused_k", "stencil3d_step_mxu_k",
           "flash_attention", "ssd_chunk_scan", "default_interpret"]
