"""Subprocess body: the dry-run machinery (build_cell: specs, shardings,
lower, compile, roofline extraction) on a small 2x4 mesh with smoke
configs — CI-speed proof that the production-path plumbing works for all
step kinds and model families."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402

import jax  # noqa: E402

from jax.sharding import AxisType  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch import specs as specs_lib  # noqa: E402
from repro.utils import hlo as hlo_lib  # noqa: E402

CELLS = [
    ("smollm-135m", "train_4k"),
    ("mixtral-8x22b", "train_4k"),      # MoE path
    ("mamba2-780m", "decode_32k"),      # SSM state cache
    ("gemma2-2b", "long_500k"),         # ring KV cache + softcap
    ("whisper-small", "decode_32k"),    # enc-dec memory_kv
    ("llava-next-34b", "prefill_32k"),  # patch prefix
]


def _shrink_shapes():
    # shrink the global shape table so smoke cells compile in seconds
    specs_lib.SHAPES.clear()
    specs_lib.SHAPES.update({
        "train_4k": (64, 8, "train"),
        "prefill_32k": (128, 8, "prefill"),
        "decode_32k": (128, 8, "decode"),
        "long_500k": (256, 8, "decode"),
    })
    import repro.configs.common as common
    common.SHAPES = specs_lib.SHAPES


def main():
    assert jax.device_count() == 8
    _shrink_shapes()
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    # monkeypatch the registry to smoke configs
    real_get = configs.get_config
    configs.get_config = lambda a: configs.reduced(real_get(a))
    specs_lib._param_struct.cache_clear()

    for arch, shape in CELLS:
        fn, args, in_sh, donate, meta = specs_lib.build_cell(
            arch, shape, mesh)
        with mesh:
            lowered = jax.jit(fn, in_shardings=in_sh,
                              donate_argnums=donate).lower(*args)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        assert mem is not None
        roof = hlo_lib.roofline_from_compiled(compiled, mesh.size)
        assert roof.flops > 0
        print(f"OK {arch} x {shape}: flops={roof.flops:.2e} "
              f"coll={roof.coll_bytes:.2e} bottleneck={roof.bottleneck}")
    print("DRYRUN_SMALL_OK")


if __name__ == "__main__":
    main()
