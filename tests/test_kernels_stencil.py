"""Fused block-level stencil kernels vs the jnp oracle (life_blocks_ref),
and end-to-end vs the BB engine through expanded space."""
import numpy as np
import pytest

from repro.core import fractals
from repro.core.baselines import BBEngine
from repro.core.compact import BlockLayout
from repro.core.stencil import SqueezeBlockEngine
from repro.kernels import ops, ref

CASES = [
    (fractals.SIERPINSKI, 5, 2),   # rho=4
    (fractals.SIERPINSKI, 6, 3),   # rho=8
    (fractals.CARPET, 3, 1),       # rho=3
    (fractals.VICSEK, 3, 1),
]
IDS = [f"{f.name}-r{r}-m{m}" for f, r, m in CASES]


STEPS = {"blocks": ops.life_step_blocks, "strips": ops.life_step_strips,
         "fused": ops.life_step_fused, "mxu": ops.stencil_step_mxu}


@pytest.mark.parametrize("frac,r,m", CASES, ids=IDS)
@pytest.mark.parametrize("variant", ["blocks", "strips", "fused", "mxu"])
def test_stencil_kernel_matches_oracle(frac, r, m, variant):
    layout = BlockLayout(frac, r, m)
    eng = SqueezeBlockEngine(layout)
    state = eng.init_random(seed=5)
    step = STEPS[variant]
    for i in range(3):
        want = ref.life_blocks_ref(layout, state)
        got = step(layout, state, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"{variant} step {i}")
        state = got


@pytest.mark.parametrize("variant", ["blocks", "strips", "fused", "mxu"])
def test_stencil_kernel_matches_bb_end_to_end(variant):
    frac, r, m = fractals.SIERPINSKI, 6, 2
    layout = BlockLayout(frac, r, m)
    bb = BBEngine(frac, r)
    step = STEPS[variant]

    s_e = bb.init_random(seed=9)
    s_b = layout.from_expanded(s_e)
    for i in range(4):
        s_e = bb.step(s_e)
        s_b = step(layout, s_b, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(layout.to_expanded(s_b)), np.asarray(s_e),
            err_msg=f"{variant} diverged from BB at step {i}")


def test_variants_agree_many_steps():
    frac, r, m = fractals.CARPET, 3, 1
    layout = BlockLayout(frac, r, m)
    eng = SqueezeBlockEngine(layout)
    s1 = eng.init_random(seed=2)
    s2 = s1
    s3 = s1
    s4 = s1
    for _ in range(10):
        s1 = ops.life_step_blocks(layout, s1, interpret=True)
        s2 = ops.life_step_strips(layout, s2, interpret=True)
        s3 = ops.life_step_fused(layout, s3, interpret=True)
        s4 = ops.stencil_step_mxu(layout, s4, interpret=True)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s3))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s4))


def test_interpret_only_on_cpu(monkeypatch):
    """Pallas kernels compile on TPU, interpret on the CPU, and never fall
    back to the interpreter silently on any other backend."""
    import jax

    from repro.kernels import common
    for backend, want in (("tpu", False), ("cpu", True)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert common.default_interpret() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(common.NoKernelBackendError):
        common.resolve_interpret(None)
    assert common.resolve_interpret(True) is True


@pytest.mark.parametrize("entry", ["blocks", "fused"])
def test_smem_table_kernels_refuse_large_layouts(entry):
    """v1/v3 scalar-prefetch the whole (nb, 8) neighbor table into SMEM:
    past its budget a compiled run raises a named error before tracing."""
    from repro.kernels.common import KernelCapacityError
    layout = BlockLayout(fractals.SIERPINSKI, 13, 4)  # nb = 3^9
    fn = {"blocks": ops.stencil_step_blocks,
          "fused": ops.stencil_step_fused}[entry]
    with pytest.raises(KernelCapacityError, match="SMEM"):
        fn(layout, None, interpret=False)
