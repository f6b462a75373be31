"""Operations one Gauss-Newton pass needs per time step.

``per_step_pass(nx, ny, linearization)`` is the number of floating-point
operations that the parallel iterated smoother requires for one time step
in one pass, under the configurations' spec (adaptive Levenberg-Marquardt
damping, so each measurement is augmented to ``m = ny + nx`` rows, and
one Gauss-Newton cost is evaluated per pass). It is a fixed count, the
same whichever lowering, scan or fusion a program uses: it counts what
the formulation requires, not what a program executes. Padded lanes and
padded steps are not counted by the callers.

Conventions (one multiply and one add are two operations):

* product of an ``a x b`` and a ``b x c`` matrix: ``2abc``; matrix times
  vector ``2ab``; sum of two ``a x b`` arrays ``ab``;
* ``n x n`` inverse: ``2n^3``; LU solve with ``r`` right-hand sides:
  ``2n^3/3 + 2n^2 r``; Cholesky: ``n^3/3``; triangular solve with one
  right-hand side: ``n^2``;
* the model functions ``f``, ``h`` and their Jacobians are not counted
  (they are the problem's, not the smoother's linear algebra).

Derivation, per step and pass (``x = nx``, ``m = ny + nx``):

1. Linearization at the previous iterate (Taylor): the offsets
   ``c = f(m) - F m`` and ``d = h(m) - H m``: ``2x^2 + x + 2 ny x + ny``.
   Statistical linear regression through the ``s = 2x`` cubature points,
   for each map ``phi: R^x -> R^z`` (``z = x`` for f, ``ny`` for h):
   Cholesky ``x^3/3``; points ``2 s x^2 + s x``; mean ``2 s z``;
   deviations ``s x + s z``; ``Psi`` ``2 s x z``; ``Phi`` ``2 s z^2``;
   ``F = (P^-1 Psi)^T`` ``2x^3/3 + 2x^2 z``; ``c`` ``2 z x + z``;
   ``Lambda = Phi - F P F^T`` ``2 z x^2 + 2 z^2 x + z^2``; then
   ``Q + Lambda_f`` and ``R + Lambda_h``: ``x^2 + ny^2``.
2. Filtering element (paper Eq. 13-14): ``S = H Q H^T + R``
   ``2 m x^2 + 2 m^2 x + m^2``; ``S^-1`` ``2m^3``; ``K = Q H^T S^-1``
   ``2 x^2 m + 2 x m^2``; innovation ``2 m x + 2m``; ``I - K H``
   ``2 x^2 m + x``; ``H F`` ``2 m x^2``; ``A`` ``2x^3``; ``b``
   ``2 x m + x``; ``C`` ``2x^3``; ``eta`` ``2m^2 + 2 m x``; ``J``
   ``2 m^2 x + 2 m x^2``.
3. Filtering combine (Eq. 15), two per step (a Blelloch scan makes about
   ``2n`` combines over ``n`` elements): ``W = I + J C`` ``2x^3 + x``;
   right-hand sides ``J b``, ``eta - J b``, ``J A`` ``2x^2 + x + 2x^3``;
   one LU solve with ``2x + 1`` right-hand sides; ``A`` ``2x^3``; ``b``
   ``4x^2 + 2x``; ``C`` ``4x^3 + x^2``; ``eta`` ``2x^2 + x``; ``J``
   ``2x^3 + x^2``.
4. Smoothing element (Eq. 17-18): ``F P`` ``2x^3``; ``P' = F P F^T + Q``
   ``2x^3 + x^2``; ``E`` by an LU solve with ``x`` right-hand sides;
   ``g`` ``4x^2 + 2x``; ``L = P - E F P`` ``2x^3 + x^2``.
5. Smoothing combine (Eq. 19), two per step: ``E`` ``2x^3``; ``g``
   ``2x^2 + x``; ``L`` ``4x^3 + x^2``.
6. Gauss-Newton cost of the candidate under its own linearization: the
   linearization of item 1 again, then the transition residual
   ``2x^2 + 2x`` and measurement residual ``2 ny x + 2 ny``, each
   weighed by a Cholesky factor, a triangular solve and a dot product:
   ``x^3/3 + x^2 + 2x`` and ``ny^3/3 + ny^2 + 2ny``.
"""
from __future__ import annotations

from fractions import Fraction


def _lu(n, r):
    return Fraction(2 * n ** 3, 3) + 2 * n * n * r


def linearization(nx: int, ny: int, kind: str) -> Fraction:
    x = nx
    if kind == "taylor":
        return Fraction(2 * x * x + x + 2 * ny * x + ny)
    if kind != "slr":
        raise ValueError(f"unknown linearization {kind!r}")
    s = 2 * x
    total = Fraction(x * x + ny * ny)
    for z in (x, ny):
        total += (Fraction(x ** 3, 3) + 2 * s * x * x + s * x + 2 * s * z
                  + s * x + s * z + 2 * s * x * z + 2 * s * z * z
                  + _lu(x, z) + 2 * z * x + z
                  + 2 * z * x * x + 2 * z * z * x + z * z)
    return total


def filtering_element(nx: int, m: int) -> Fraction:
    x = nx
    return Fraction(
        (2 * m * x * x + 2 * m * m * x + m * m) + 2 * m ** 3
        + (2 * x * x * m + 2 * x * m * m) + (2 * m * x + 2 * m)
        + (2 * x * x * m + x) + 2 * m * x * x + 2 * x ** 3
        + (2 * x * m + x) + 2 * x ** 3 + (2 * m * m + 2 * m * x)
        + (2 * m * m * x + 2 * m * x * x))


def filtering_combine(nx: int) -> Fraction:
    x = nx
    return ((2 * x ** 3 + x) + (2 * x * x + x + 2 * x ** 3)
            + _lu(x, 2 * x + 1) + 2 * x ** 3 + (4 * x * x + 2 * x)
            + (4 * x ** 3 + x * x) + (2 * x * x + x) + (2 * x ** 3 + x * x))


def smoothing_element(nx: int) -> Fraction:
    x = nx
    return (2 * x ** 3 + (2 * x ** 3 + x * x) + _lu(x, x)
            + (4 * x * x + 2 * x) + (2 * x ** 3 + x * x))


def smoothing_combine(nx: int) -> Fraction:
    x = nx
    return Fraction(2 * x ** 3 + (2 * x * x + x) + (4 * x ** 3 + x * x))


def cost(nx: int, ny: int, kind: str) -> Fraction:
    x = nx
    return (linearization(nx, ny, kind) + (2 * x * x + 2 * x)
            + (2 * ny * x + 2 * ny) + Fraction(x ** 3, 3) + x * x + 2 * x
            + Fraction(ny ** 3, 3) + ny * ny + 2 * ny)


def per_step_pass(nx: int, ny: int, kind: str) -> float:
    """Operations per time step per Gauss-Newton pass (items 1 to 6)."""
    m = ny + nx
    return float(linearization(nx, ny, kind) + filtering_element(nx, m)
                 + 2 * filtering_combine(nx) + smoothing_element(nx)
                 + 2 * smoothing_combine(nx) + cost(nx, ny, kind))
