"""Subprocess body for the pipeline-parallel test (4 placeholder devices,
4 stages): GPipe microbatched apply must equal sequential layer apply."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime.pipeline_parallel import pipeline_apply, stack_stages  # noqa: E402
from jax.sharding import AxisType  # noqa: E402


def main():
    assert jax.device_count() == 4
    mesh = jax.make_mesh((4,), ("pod",),
                         axis_types=(AxisType.Auto,))
    n_layers, d, b = 8, 32, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (n_layers, d, d)) * 0.3
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, d))

    def layer(w, h):
        return jnp.tanh(h @ w)

    # sequential reference
    ref = x
    for i in range(n_layers):
        ref = layer(ws[i], ref)

    def stage_fn(stage_params, h):
        def body(carry, w):
            return layer(w, carry), None
        out, _ = jax.lax.scan(body, h, stage_params["w"])
        return out

    stages = stack_stages({"w": ws}, 4)["w"]  # (4, 2, d, d)
    got = pipeline_apply(stage_fn, {"w": stages}, x, mesh=mesh,
                         axis="pod", n_microbatches=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    print("PP_OK: pipelined == sequential over", n_layers, "layers")


if __name__ == "__main__":
    main()
