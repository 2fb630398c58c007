"""Run one cell of the chip benchmark:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. The cell is an entry of `BENCHMARK.json`; everything it
needs is found by name under `chipbench/`. The last line of standard
output is one JSON object (`correct`, `attempted`, `failed`, `metrics`,
`device`, with `--trace 1` also `breakdown`, and last `check`: each
number compared with the plain reference, beside its limit). Exits 2,
with no result, where JAX finds no TPU, too few chips, or a device kind
that `chipbench/peaks.json` does not list.

JAX's compilation cache is kept in `<checkout>/.jax_cache`
(`harness.use_compile_cache`), so only the first run of a cell in a
checkout compiles.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness
    harness.use_compile_cache(ROOT)
    return harness.main(args, ROOT, T_START)


if __name__ == "__main__":
    sys.exit(main())
