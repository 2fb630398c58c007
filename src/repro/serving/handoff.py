"""The result hand-off: how a row's state leaves the chip.

A TPU stores an array in tiles of (8, 128) 32-bit words, puts its
largest axis on the 128 lanes, and packs 1- and 2-byte elements several
to a word. A block state (``u8[n_blocks, rho, rho]``) lives with its
block axis on the lanes and four cells of its last axis to a word, so
its bytes in HBM are not in row-major order. Copied to the host as it
is, the runtime unpacks and untiles every byte on the host while the
chip waits: a v5e host took 2.4-2.9 s for four 408 MB Life rows.

``tile_linear`` rewrites such a state on the chip into a ``(X, 128)``
array of ``uint32`` words, ``X`` a multiple of 8: a single column of
whole tiles with no sub-word packing, whose bytes in HBM are already in
row-major order. The host copies it at the speed it copies memory
(0.12-0.17 s a row) and reads the words back as the state's dtype and
shape with views that copy nothing (``_from_words``). States of 32-bit
or wider elements take the plain copy (``host_copy``).
"""
from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs

LANES = 128
#: 32-bit words in one (8, 128) tile
TILE_WORDS = 8 * LANES

#: the most of a state ``host_pieces`` relays out at once: the relayout's
#: temporaries take about three times its input (described-v5e compiles:
#: 1.22 GB for a 408 MB Life row, 8.27 GB for a 2.76 GB shard of the
#: r=20 domain), which a chip holding the domain cannot spare, and its
#: compile time grows with its size (5.6 s at 256 MiB of u8 blocks, 22 s
#: at 1 GiB, on a described v5e)
PIECE_BYTES = 1 << 28


def _row_major(x: jax.Array) -> jax.Array:
    """``x.reshape(-1)``, written as a transpose of its largest axis back
    from last place. The TPU keeps that axis on the lanes, so the
    transpose is one pass over the data; a plain ``reshape(-1)`` of a
    ``(2, n, 9, 9)`` state is first relaid out with the 9 x 9 axes
    minor, each padded to a whole tile, which overflows HBM."""
    a = int(np.argmax(x.shape)) if x.ndim else 0
    if a >= x.ndim - 1:
        return x.reshape(-1)
    lead, n, tail = (math.prod(x.shape[:a]), x.shape[a],
                     math.prod(x.shape[a + 1:]))
    z = jnp.moveaxis(x, a, -1).reshape(lead, tail, n)
    return jnp.swapaxes(z, 1, 2).reshape(-1)


@jax.jit
def tile_linear(x: jax.Array) -> jax.Array:
    """``x``'s bytes in row-major order as ``(X, 128)`` ``uint32``
    words, ``X`` a multiple of 8, zero-padded at the end; the first
    element of each word in its low bits (the host's little-endian
    order)."""
    per = 4 // x.dtype.itemsize           # elements per word
    unsigned = jnp.dtype(f"uint{32 // per}")
    x = jnp.atleast_1d(x.astype(unsigned) if x.dtype == jnp.bool_
                       else lax.bitcast_convert_type(x, unsigned))
    if x.shape[-1] % per:
        # a word would straddle two rows of the last axis: flatten first
        flat = _row_major(x)
        flat = jnp.pad(flat, (0, -flat.size % (per * TILE_WORDS)))
        x = flat.reshape(-1, per * LANES)
    # packing along the last axis before the transpose is 5x faster on a
    # v5e for a Life row (the chip packs that axis into words already),
    # and the transpose then moves 32-bit words
    words = functools.reduce(operator.or_, (
        lax.slice_in_dim(x, k, None, per, x.ndim - 1).astype(jnp.uint32)
        << (32 // per * k) for k in range(per)))
    flat = _row_major(words)
    flat = jnp.pad(flat, (0, -flat.size % TILE_WORDS))
    return flat.reshape(-1, LANES)


def _from_words(words: np.ndarray, shape, dtype) -> np.ndarray:
    """The array ``tile_linear`` packed, as views of ``words``."""
    dtype = np.dtype(dtype)
    n = math.prod(shape) * dtype.itemsize
    return words.reshape(-1).view(np.uint8)[:n].view(dtype).reshape(shape)


def start_host_copy(state: jax.Array):
    """Start copying a device array to the host, bit for bit, and return
    a function that waits for the copy and returns the host array.
    Elements narrower than a word, which the chip packs several to a
    word, go through ``tile_linear`` (path ``flat``); 32-bit and wider
    ones are copied as they are (path ``plain``): the host untiles
    32-bit tiles at the speed it copies, so relaying them out first
    gains nothing (a v5e moved a 1.36 GB ``f32`` row in 0.56-0.63 s
    either way, a ``bf16`` one in 0.36-0.40 s flat against 1.05-1.08 s
    plain). Counts ``serve.host_copies{path}`` and
    ``serve.host_copy_bytes{path}`` when the copy lands."""
    flat = state.dtype.itemsize < 4
    dev = tile_linear(state) if flat else state
    dev.copy_to_host_async()

    def finish() -> np.ndarray:
        host = np.asarray(jax.device_get(dev))
        if flat:
            host = _from_words(host, state.shape, state.dtype)
        path = "flat" if flat else "plain"
        obs.inc("serve.host_copies", path=path)
        obs.inc("serve.host_copy_bytes", host.nbytes, path=path)
        return host

    return finish


def host_copy(state: jax.Array) -> np.ndarray:
    """Host copy of a device array (``start_host_copy``, waited for)."""
    return start_host_copy(state)()


@functools.partial(jax.jit, static_argnums=(2, 3))
def _piece(x: jax.Array, start, size: int, axis: int) -> jax.Array:
    return lax.dynamic_slice_in_dim(x, start, size, axis)


def host_pieces(state: jax.Array, axis: int):
    """Copy ``state`` to the host in pieces along ``axis``, each at most
    ``PIECE_BYTES`` and all of one size (the last one overlaps the one
    before it), the next piece's copy started before a piece is
    yielded. Yields ``(start, stop, host piece)``."""
    n = state.shape[axis]
    size = max(1, min(n, PIECE_BYTES * n // max(state.nbytes, 1)))
    starts = list(range(0, n - size, size)) + [n - size]
    pending = None
    for start in starts:
        piece = state if size == n else _piece(state, start, size,
                                               axis % state.ndim)
        ahead = (start, start_host_copy(piece))
        if pending is not None:
            yield pending[0], pending[0] + size, pending[1]()
        pending = ahead
    yield pending[0], pending[0] + size, pending[1]()
