"""Benchmark orchestrator — one module per paper table/figure plus the
roofline analysis. Prints ``name,us_per_call,derived`` CSV rows."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    print("name,us_per_call,derived")
    from benchmarks import (fig10_mrf, fig13_speedup, fig14_tensorcore,
                            roofline, stencil_traffic, table2_memory)
    modules = [
        ("fig10_mrf", fig10_mrf.run),
        # fig13 runs fig12 internally (shares timings)
        ("fig12+fig13", fig13_speedup.run),
        ("fig14_tensorcore", fig14_tensorcore.run),
        ("table2_memory", table2_memory.run),
        ("stencil_traffic", stencil_traffic.run),
        ("roofline-single-pod", lambda: roofline.run("16x16")),
        ("roofline-multi-pod", lambda: roofline.run("2x16x16")),
        ("roofline-validate", roofline.validate_analytic_vs_compiled),
    ]
    failed = []
    for name, fn in modules:
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
