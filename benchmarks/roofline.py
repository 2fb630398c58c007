"""Roofline analysis (EXPERIMENTS.md §Roofline): merge the compiled
dry-run results (memory fit + collective inventory; dryrun_results.jsonl)
with the analytic three-term model (utils/analytic.py), which is
authoritative for FLOPs/bytes because XLA's cost_analysis counts loop
bodies once (verified; see utils/analytic.py docstring).

Prints one CSV row per (arch x shape x mesh) with the three terms, the
dominant bottleneck, MODEL_FLOPS, the useful-flops ratio, and the
MFU bound implied by the dominant term.
"""
from __future__ import annotations

import json
import os

from repro import configs
from repro.utils import analytic
from benchmarks.common import emit

RESULTS = os.path.join(os.path.dirname(__file__), "..",
                       "dryrun_results.jsonl")


def load_dryrun(path=RESULTS):
    rows = {}
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                rows[(r.get("arch"), r.get("shape"), r.get("mesh"))] = r
    return rows


def run(mesh_name: str = "16x16"):
    compiled = load_dryrun()
    mesh = (analytic.MeshModel(pod=2) if mesh_name == "2x16x16"
            else analytic.MeshModel())
    for arch, shape, _ in configs.cells():
        cfg = configs.get_config(arch)
        roof = analytic.analytic_roofline(cfg, shape, mesh)
        c = compiled.get((arch, shape, mesh_name), {})
        fit = c.get("per_device_bytes")
        fit_s = f"{fit / 2 ** 30:.1f}GiB" if fit else "n/a"
        ok = c.get("ok", False)
        emit(
            f"roofline/{mesh_name}/{arch}/{shape}", None,
            f"t_comp={roof.t_compute:.4f}s;t_mem={roof.t_memory:.4f}s;"
            f"t_coll={roof.t_collective:.4f}s;bound={roof.bottleneck};"
            f"model_flops={roof.model_flops:.3e};"
            f"useful_ratio={roof.useful_flops_ratio:.2f};"
            f"mfu_bound={roof.mfu_bound:.3f};compiled_ok={ok};"
            f"per_dev={fit_s}")


def validate_analytic_vs_compiled():
    """Spot-check: for a no-layer-scan model variant the compiled flops
    should track the analytic forward flops (run by tests)."""
    import dataclasses
    import jax
    from repro.models import model as model_lib

    cfg = configs.get_smoke_config("tinyllama-1.1b")
    cfg = dataclasses.replace(cfg, n_units=1, remat="none")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    import jax.numpy as jnp
    batch = {"tokens": jnp.zeros((2, 128), jnp.int32),
             "labels": jnp.zeros((2, 128), jnp.int32)}

    def fwd(p, b):
        return model_lib.forward(p, b, cfg)[0]

    comp = jax.jit(fwd).lower(params, batch).compile()
    flops = (comp.cost_analysis() or {}).get("flops", 0.0)
    # analytic fwd matmul flops for this tiny config
    n = cfg.param_count()
    tokens = 2 * 128
    approx = 2.0 * n * tokens
    ratio = flops / approx
    emit("roofline/validate/no-scan-fwd", None,
         f"hlo={flops:.3e};analytic2ND={approx:.3e};ratio={ratio:.2f}")
    return ratio


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    run("16x16")
    run("2x16x16")
    validate_analytic_vs_compiled()
