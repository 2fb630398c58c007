"""The two Squeeze space maps: lambda(w) (compact -> expanded) and nu(w)
(expanded -> compact), paper Sections 3.3 and 3.4.

Conventions (paper Section 3.4): origin (0,0) at the upper-left of both the
expanded domain D^2 (side n = s**r) and the compact domain D_c^2
(k^floor(r/2) rows x k^ceil(r/2) cols); x grows right, y grows down.

Digit structure. A compact coordinate interleaves base-k digits across levels:
odd levels mu = 1,3,5,... are the base-k digits of x (digit (mu-1)//2), even
levels mu = 2,4,... the digits of y. An expanded coordinate's level-mu replica
slot is its base-s digit mu-1 per axis (paper Eq. 6; the printed denominator
``s^mu`` is a typo for ``s^(mu-1)``, otherwise theta would always be 0).

NOTE on the paper's Eqs. 8-10: as printed, f_x selects *even* levels, which
contradicts Eq. 5's beta_mu (odd levels read w_x) and Section 3.1 ("at mu=1 the
compact space is scaled up in x"). We implement the self-consistent version —
odd levels accumulate into x, even into y — which is the unique choice making
nu the inverse of lambda; the property tests enforce ``nu . lambda = id``.

Three implementations per map:
  * ``*_scalar``  — pure-python ints, the executable spec (hypothesis oracle);
  * ``lambda_map`` / ``nu_map`` — vectorised jnp (per-level unrolled loop);
  * ``*_matmul``  — the tensor-core/MXU encoding (paper Section 3.6, Eqs.
    15-16): replica codes matrix @ per-level weight matrix, fp32 accumulate.
    Exact while every product < 2**24 (holds for all supported n <= 2**20).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.fractals import NBBFractal

Array = jnp.ndarray


# ======================================================================
# scalar references (executable spec)
# ======================================================================
def lambda_map_scalar(frac: NBBFractal, r: int, cx: int, cy: int
                      ) -> Tuple[int, int]:
    """Paper Eqs. 2-5: compact (cx, cy) -> expanded (ex, ey)."""
    ex = ey = 0
    for mu in range(1, r + 1):
        w = cx if (mu % 2 == 1) else cy
        beta = (w // frac.k ** ((mu - 1) // 2)) % frac.k
        tx, ty = frac.positions[beta]
        ex += tx * frac.s ** (mu - 1)
        ey += ty * frac.s ** (mu - 1)
    return ex, ey


def nu_map_scalar(frac: NBBFractal, r: int, ex: int, ey: int
                  ) -> Tuple[int, int]:
    """Paper Eqs. 6-13: expanded (ex, ey) -> compact (cx, cy).

    Only meaningful when (ex, ey) is a fractal cell (see is_fractal_scalar);
    for holes the H_nu lookup is -1 and the result is unspecified (clamped
    to code 0 here, matching the vectorised path).
    """
    cx = cy = 0
    for mu in range(1, r + 1):
        tx = (ex // frac.s ** (mu - 1)) % frac.s
        ty = (ey // frac.s ** (mu - 1)) % frac.s
        code = int(frac.h_nu[ty, tx])
        code = max(code, 0)
        delta = frac.k ** ((mu - 1) // 2)
        if mu % 2 == 1:
            cx += code * delta
        else:
            cy += code * delta
    return cx, cy


def is_fractal_scalar(frac: NBBFractal, r: int, ex: int, ey: int) -> bool:
    if not (0 <= ex < frac.s ** r and 0 <= ey < frac.s ** r):
        return False
    for mu in range(1, r + 1):
        tx = (ex // frac.s ** (mu - 1)) % frac.s
        ty = (ey // frac.s ** (mu - 1)) % frac.s
        if frac.h_nu[ty, tx] < 0:
            return False
    return True


# ======================================================================
# vectorised jnp maps
# ======================================================================
def lambda_map(frac: NBBFractal, r: int, cx: Array, cy: Array
               ) -> Tuple[Array, Array]:
    """Vectorised lambda(w). cx/cy: int32 arrays of any (matching) shape.
    The slot of replica ``beta`` is looked up per axis (two (k,) tables,
    not one (k, 2) table): a (..., 2) lookup result has a minor axis of
    2, which a TPU pads to 128 lanes."""
    hx, hy = (jnp.asarray(frac.h_lambda[:, a]) for a in (0, 1))
    cx = cx.astype(jnp.int32)
    cy = cy.astype(jnp.int32)
    ex = jnp.zeros_like(cx)
    ey = jnp.zeros_like(cy)
    for mu in range(1, r + 1):
        w = cx if (mu % 2 == 1) else cy
        beta = (w // (frac.k ** ((mu - 1) // 2))) % frac.k
        scale = frac.s ** (mu - 1)
        ex = ex + hx[beta] * scale
        ey = ey + hy[beta] * scale
    return ex, ey


def _nu_levels(frac: NBBFractal, r: int, ex: Array, ey: Array):
    """The per-level replica codes H_nu[theta_mu], mu = 1..r, one array
    shaped like ``ex`` each (holes -1). Consumed level by level, so that
    no (..., r) array of all levels is held: its minor axis of r would
    be padded to 128 lanes on a TPU."""
    hn = jnp.asarray(frac.h_nu)  # (s, s) indexed [y, x]
    ex = ex.astype(jnp.int32)
    ey = ey.astype(jnp.int32)
    for mu in range(1, r + 1):
        scale = frac.s ** (mu - 1)
        yield hn[(ey // scale) % frac.s, (ex // scale) % frac.s]


def _nu_codes(frac: NBBFractal, r: int, ex: Array, ey: Array) -> Array:
    """Per-level replica codes H_nu[theta_mu], shape (..., r) int32.

    Holes produce -1 (useful for membership tests); nu_map clamps to 0.
    """
    return jnp.stack(list(_nu_levels(frac, r, ex, ey)), axis=-1)


def _nu_fold(frac: NBBFractal, r: int, ex: Array, ey: Array):
    """(cx, cy, all levels occupied) from one pass over the levels."""
    wx, wy = nu_weights(frac, r)
    cx = cy = jnp.zeros(jnp.shape(ex), jnp.int32)
    occupied = jnp.ones(jnp.shape(ex), bool)
    for mu, code in enumerate(_nu_levels(frac, r, ex, ey)):
        occupied = occupied & (code >= 0)
        code = jnp.maximum(code, 0)
        cx = cx + code * int(wx[mu])
        cy = cy + code * int(wy[mu])
    return cx, cy, occupied


def nu_map(frac: NBBFractal, r: int, ex: Array, ey: Array
           ) -> Tuple[Array, Array]:
    """Vectorised nu(w). ex/ey: int32 arrays of any (matching) shape."""
    cx, cy, _ = _nu_fold(frac, r, ex, ey)
    return cx, cy


def _clipped(frac: NBBFractal, r: int, ex: Array, ey: Array):
    """(in bounds, ex clipped, ey clipped) against the level-r side."""
    n = frac.s ** r
    in_bounds = (ex >= 0) & (ex < n) & (ey >= 0) & (ey < n)
    return in_bounds, jnp.clip(ex, 0, n - 1), jnp.clip(ey, 0, n - 1)


def is_fractal(frac: NBBFractal, r: int, ex: Array, ey: Array) -> Array:
    """Vectorised fractal-membership test for expanded coordinates."""
    in_bounds, exc, eyc = _clipped(frac, r, ex, ey)
    return in_bounds & _nu_fold(frac, r, exc, eyc)[2]


def nu_with_membership(frac: NBBFractal, r: int, ex: Array, ey: Array
                       ) -> Tuple[Array, Array, Array]:
    """Fused nu(w) + membership: one digit pass serves both (the stencil
    inner loop needs both per neighbor, so computing codes twice would
    double the map cost). Returns (cx, cy, valid)."""
    in_bounds, exc, eyc = _clipped(frac, r, ex, ey)
    cx, cy, occupied = _nu_fold(frac, r, exc, eyc)
    return cx, cy, in_bounds & occupied


# ======================================================================
# matmul (tensor-core / MXU) encodings — paper Section 3.6
# ======================================================================
def nu_weights(frac: NBBFractal, r: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-level nu weights (Delta^nu_mu * f(mu)), as two (r,) fp32 vectors.

    Row mu-1 holds k^floor((mu-1)/2), routed to x for odd mu, y for even mu
    (the self-consistent form of paper Eqs. 7-10; see module docstring).
    """
    wx = np.zeros((r,), np.float32)
    wy = np.zeros((r,), np.float32)
    for mu in range(1, r + 1):
        delta = float(frac.k ** ((mu - 1) // 2))
        if mu % 2 == 1:
            wx[mu - 1] = delta
        else:
            wy[mu - 1] = delta
    return wx, wy


def nu_weight_matrix(frac: NBBFractal, r: int) -> np.ndarray:
    """(r, 2) fp32 — the ``A`` operand of the paper's MMA encoding (Eq. 15),
    transposed to the (codes @ W) orientation used on the MXU."""
    wx, wy = nu_weights(frac, r)
    return np.stack([wx, wy], axis=1)


def nu_map_matmul(frac: NBBFractal, r: int, ex: Array, ey: Array
                  ) -> Tuple[Array, Array]:
    """nu(w) as one fp32 matmul: codes (N, r) @ W (r, 2) -> (N, 2).

    This is the paper's tensor-core formulation (Eqs. 15-16) adapted to the
    MXU: one dot maps a whole batch of coordinates. Exact for n <= 2**20.
    """
    codes = jnp.maximum(_nu_codes(frac, r, ex, ey), 0).astype(jnp.float32)
    w = jnp.asarray(nu_weight_matrix(frac, r))  # (r, 2)
    out = codes @ w  # MXU dot, fp32 accumulate
    return (out[..., 0].astype(jnp.int32), out[..., 1].astype(jnp.int32))


def lambda_weight_matrix(frac: NBBFractal, r: int) -> np.ndarray:
    """(2r, 2) fp32 block-diagonal weights for the single-dot lambda form:
    [tau_x codes | tau_y codes] (N, 2r) @ W -> (ex, ey)."""
    w = np.zeros((2 * r, 2), np.float32)
    for mu in range(1, r + 1):
        w[mu - 1, 0] = float(frac.s ** (mu - 1))
        w[r + mu - 1, 1] = float(frac.s ** (mu - 1))
    return w


def lambda_codes(frac: NBBFractal, r: int, cx: Array, cy: Array) -> Array:
    """(..., 2r) fp32: per-level tau_x then tau_y slot offsets of beta_mu."""
    h = jnp.asarray(frac.h_lambda)
    cx = cx.astype(jnp.int32)
    cy = cy.astype(jnp.int32)
    tx, ty = [], []
    for mu in range(1, r + 1):
        w = cx if (mu % 2 == 1) else cy
        beta = (w // (frac.k ** ((mu - 1) // 2))) % frac.k
        tau = h[beta]
        tx.append(tau[..., 0])
        ty.append(tau[..., 1])
    return jnp.stack(tx + ty, axis=-1).astype(jnp.float32)


def lambda_map_matmul(frac: NBBFractal, r: int, cx: Array, cy: Array
                      ) -> Tuple[Array, Array]:
    """lambda(w) as one fp32 matmul (the [7]-style tensor-core encoding)."""
    codes = lambda_codes(frac, r, cx, cy)  # (..., 2r)
    w = jnp.asarray(lambda_weight_matrix(frac, r))  # (2r, 2)
    out = codes @ w
    return (out[..., 0].astype(jnp.int32), out[..., 1].astype(jnp.int32))
