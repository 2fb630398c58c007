"""The result hand-off (``repro.serving.handoff``): every state the
service returns, snapshots or checkpoints leaves the device through
``host_copy``, relaid out into tile-linear 32-bit words first where its
elements are narrower than a word. The host must get, bit for bit, the
array the plain copy ``np.asarray(jax.device_get(state))`` gives."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.checkpoint.manager import CheckpointManager
from repro.core import fractals
from repro.serving import FractalService, ServiceConfig, SimRequest, handoff
from repro.workloads import GRAY_SCOTT, LIFE

#: rows shaped like the engines' states: a Life block row with an odd
#: block count, a two-channel Gray-Scott row, a 3-D block row, and a row
#: of 105 elements (no multiple of 4 bytes, nor of 8 x 128 words)
SHAPES = {"life-odd-blocks": (243, 16, 16),
          "gray-scott-2ch": (2, 64, 9, 9),
          "block-3d": (20, 9, 9, 9),
          "ragged": (7, 3, 5)}
DTYPES = [jnp.uint8, jnp.int32, jnp.float32, jnp.bfloat16, jnp.bool_]


def _random_bits(shape, dtype, seed=0) -> np.ndarray:
    """Every bit pattern of the dtype, NaN payloads included."""
    dtype = np.dtype(dtype)
    n = int(np.prod(shape)) * dtype.itemsize
    raw = np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)
    if dtype == np.bool_:
        raw &= 1  # a bool's byte is 0 or 1
    return raw.view(dtype).reshape(shape)


def _copies(reg, path) -> int:
    """``serve.host_copies{path}``; ``obs.reset`` zeroes, not drops."""
    return reg.value("serve.host_copies", path=path) or 0


def _assert_same_bits(got: np.ndarray, want: np.ndarray) -> None:
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_flat_copy_is_bit_identical_to_the_plain_copy(shape, dtype):
    state = jnp.asarray(_random_bits(shape, dtype))
    _assert_same_bits(handoff.host_copy(state),
                      np.asarray(jax.device_get(state)))


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_relayout_is_whole_tiles_of_32_bit_words(shape, dtype):
    words = handoff.tile_linear(jnp.zeros(shape, dtype))
    assert words.dtype.itemsize == 4
    rows, lanes = words.shape
    assert lanes == handoff.LANES and rows % 8 == 0
    n_bytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    # padding stays under one tile
    assert 0 <= words.nbytes - n_bytes < 4 * handoff.TILE_WORDS


@pytest.mark.parametrize("dtype", [np.int32, np.float32, np.complex64],
                         ids=lambda d: np.dtype(d).name)
def test_word_wide_elements_take_the_plain_copy(dtype):
    want = _random_bits((5, 7), dtype)
    state = jnp.asarray(want)
    with obs.enabled_scope() as reg:
        obs.reset()
        _assert_same_bits(handoff.host_copy(state),
                          np.asarray(jax.device_get(state)))
        assert (_copies(reg, "flat"), _copies(reg, "plain")) == (0, 1)


def test_each_hand_off_counts_once_on_its_path():
    flat = jnp.asarray(_random_bits((243, 16, 16), np.uint8))
    plain = jnp.zeros((2, 64, 9, 9), jnp.float32)
    with obs.enabled_scope() as reg:
        obs.reset()
        handoff.host_copy(flat)
        handoff.host_copy(flat)
        handoff.host_copy(plain)
        assert (_copies(reg, "flat"), _copies(reg, "plain")) == (2, 1)
        nbytes = {p: reg.value("serve.host_copy_bytes", path=p)
                  for p in ("flat", "plain")}
        assert nbytes == {"flat": 2 * 243 * 256, "plain": 2 * 64 * 81 * 4}


# ---------------------------------------------------------------- service
def _serve_one(tmp_path, wl, name):
    """One request with snapshots and checkpoints; then the same rid on
    a fresh service, which completes from the final checkpoint."""
    cfg = ServiceConfig(max_batch=2, hang_threshold_s=5.0,
                        ckpt_dir=str(tmp_path / name))

    def req():
        return SimRequest(frac=fractals.SIERPINSKI, r=4, m=1, steps=12,
                          snapshot_every=4, workload=wl, seed=3,
                          rid="handoff")

    [res] = FractalService(cfg).serve([req()])
    mgr = CheckpointManager(os.path.join(cfg.ckpt_dir, "handoff"))
    ckpts = {s: mgr.restore({"state": res.state}, step=s)["state"]
             for s in mgr.all_steps()}
    [again] = FractalService(cfg).serve([req()])
    return res, ckpts, again


@pytest.mark.parametrize("wl", [LIFE, GRAY_SCOTT],
                         ids=["life", "gray-scott"])
def test_service_results_snapshots_and_checkpoints_unchanged(
        tmp_path, monkeypatch, wl):
    with obs.enabled_scope() as reg:
        obs.reset()
        res, ckpts, again = _serve_one(tmp_path, wl, "handoff")
        # two snapshots and the final state, then the resumed final
        # state: u8 Life through the relayout, f32 Gray-Scott plain
        path = "flat" if np.dtype(wl.dtype).itemsize < 4 else "plain"
        assert _copies(reg, path) == 4
        assert _copies(reg, "flat") + _copies(reg, "plain") == 4
    with monkeypatch.context() as m:
        m.setattr(handoff, "host_copy",
                  lambda s: np.asarray(jax.device_get(s)))
        ref, ref_ckpts, ref_again = _serve_one(tmp_path, wl, "plain")
    assert res.ok and again.ok and again.steps_done == 12
    _assert_same_bits(res.state, ref.state)
    assert [s for s, _ in res.snapshots] == [s for s, _ in ref.snapshots]
    for (_, snap), (_, want) in zip(res.snapshots, ref.snapshots):
        _assert_same_bits(snap, want)
    assert sorted(ckpts) == sorted(ref_ckpts) and 12 in ckpts
    for s in ckpts:
        _assert_same_bits(np.asarray(ckpts[s]), np.asarray(ref_ckpts[s]))
    _assert_same_bits(again.state, ref.state)
    _assert_same_bits(ref_again.state, ref.state)
