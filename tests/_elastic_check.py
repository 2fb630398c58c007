"""Subprocess body for the elastic-restore test: a checkpoint written by a
single-device run restores onto an 8-device mesh with production
shardings, trains on, and the losses continue sanely."""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402

from jax.sharding import AxisType  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro import configs  # noqa: E402
from repro.checkpoint.manager import CheckpointManager  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.utils.sharding import param_specs  # noqa: E402


def main(ckpt_dir):
    assert jax.device_count() == 8
    cfg = configs.get_smoke_config("smollm-135m")
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    params_like = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    opt_like = adamw.init(adamw.AdamWConfig(), params_like)
    mgr = CheckpointManager(ckpt_dir)

    specs = param_specs(params_like, mesh)
    flat_specs = {}
    import jax.tree_util as jtu
    for path, s in jtu.tree_flatten_with_path(specs)[0]:
        name = "__".join(str(getattr(k, "key", getattr(k, "idx", k)))
                         for k in path)
        flat_specs["params__" + name] = s

    def put(name, arr):
        # elastic restore: device_put with the NEW mesh's sharding
        spec = flat_specs.get(name)
        if spec is not None:
            return jax.device_put(
                arr, jax.sharding.NamedSharding(mesh, spec))
        return jax.device_put(arr)

    state = mgr.restore({"params": params_like, "opt": opt_like}, put=put)
    # restored leaves are sharded over the 8-device mesh
    some = jax.tree.leaves(state["params"])[0]
    assert len(some.sharding.device_set) >= 1
    # continue training one step under the mesh
    from repro.data.pipeline import SyntheticMarkov
    from repro.launch import specs as specs_lib
    data = SyntheticMarkov(vocab=cfg.vocab, seq_len=16, global_batch=4,
                           seed=3)
    step = jax.jit(specs_lib.make_train_step(
        cfg, adamw.AdamWConfig(), mesh))
    opt_state = jax.tree.map(lambda a: jax.numpy.array(a, copy=True),
                             state["opt"])
    with mesh:
        p, o, m = step(state["params"], opt_state, data.batch(0))
    assert np.isfinite(float(m["loss"]))
    print(f"ELASTIC_OK loss={float(m['loss']):.4f}")


if __name__ == "__main__":
    main(sys.argv[1])
