"""Geometric multigrid for the face-conductance thermal operator.

The steady-state system ``G T = P`` and every implicit transient step
``(C/dt + theta G) delta = r`` share one operator family: a 7-point
face-conductance stencil (``thermal.apply_operator_fields``) plus an
optional extra diagonal (the capacity term).  Jacobi-PCG solves them in
O(n) iterations per digit — the cost wall every sweep scenario bottoms
out in (ISSUE 4).  This module adds the asymptotically right tool:

**Hierarchy.**  Levels coarsen the *lateral* grid only (2x2 cell
aggregation; the few-layer stack axis stays resolved — classic
semi-coarsening, correct here because lateral sheet conductance
dominates the thinned-die vertical coupling at fine grids).  The coarse
operator is the **Galerkin product** ``R G P`` with piecewise-constant
prolongation ``P`` (inject the coarse value into its 2x2 fine cells) and
restriction ``R = P^T`` (sum the 2x2 residuals).  For a conductance
stencil that product stays *in the family*: the coarse face conductance
is the sum of the fine faces crossing the coarse interface, the coarse
diagonal terms (package lump, capacity) are 2x2 sums — so one stencil
implementation serves every level, and void margin cells coarsen to
void coarse cells for free (zero faces stay zero).  The identity
``G_c v = R (G (P v))`` is pinned by ``tests/test_multigrid.py``; the
*deployed* hierarchy additionally halves the lateral sums back to the
true 2h spec-built stencil (see :func:`coarsen`).

**Smoother.**  Red-black *z-line* Gauss-Seidel: cells are colored by
in-plane parity ``(y + x) % 2`` (all lateral neighbors of a red cell are
black), and each half-sweep solves every colored column's vertical
tridiagonal system *exactly* (Thomas; the stack axis is 5-9 layers, so
the solve is a short unrolled loop).  Line relaxation in z keeps the
smoother robust when the vertical coupling grows relative to the
aggregated lateral faces on coarse levels.  The Pallas kernel path lives
in ``kernels/mg_smooth`` (this module is its jnp oracle).

**Cycles.**  ``v_cycle`` is the symmetric V(nu1, nu2) cycle: pre-smooth
red->black, post-smooth black->red, and an exact (dense-Cholesky)
coarsest-level solve — required because the stack couples to ambient
only through the tiny package conductance, leaving a near-null global
mode that relaxation alone cannot contract.  The cycle is therefore a
fixed SPD linear operator usable two ways:

- ``mg_solve_fields`` — stand-alone V-cycle iteration to a residual
  tolerance (``mg_fixed``/``iterate_fixed``: fixed cycle count,
  scannable/vmappable — the implicit transient stepper's inner solve);
- ``mgcg_solve_fields`` — V-cycle-preconditioned CG (``thermal.pcg``
  accepts a callable preconditioner) for the steady solve.

``thermal.steady_state(solver=...)`` selects between "pcg", "mg" and
"mgcg"; DESIGN.md §7.5 documents the selection guidance.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import obs

#: stop coarsening below this in-plane size (the coarsest level is
#: relaxed with palindromic red-black line sweeps, which is exact in the
#: limit of a 1x1 plane and near-exact at 4x4)
MIN_COARSE_N = 4

#: red-black line sweeps on the coarsest level (palindromic: k pairs
#: red->black then k pairs black->red, keeping the cycle symmetric)
N_COARSE_SWEEPS = 8

_FACES = ("gx_lf", "gx_rt", "gy_up", "gy_dn", "gz_up", "gz_dn", "g_pkg")


def operator(v: jax.Array, F: dict, d_extra) -> jax.Array:
    """(G + diag(d_extra)) @ v for one level's face fields."""
    from repro.core.thermal import apply_operator_fields
    return apply_operator_fields(v, F) + d_extra * v


def diagonal(F: dict, d_extra) -> jax.Array:
    """Exact diagonal of the level operator (0-safe for void cells)."""
    d = (F["gx_lf"] + F["gx_rt"] + F["gy_up"] + F["gy_dn"]
         + F["gz_up"] + F["gz_dn"] + F["g_pkg"] + d_extra)
    return d


# ---------------------------------------------------------------------------
# Galerkin (aggregation) coarsening — stays in the face-conductance family
# ---------------------------------------------------------------------------

def coarsen(F: dict, d_extra: jax.Array, rescale_lateral: bool = False
            ) -> tuple[dict, jax.Array]:
    """One 2x2 lateral aggregation level: ``(R G P, R d_extra P)``.

    Coarse face = sum of the fine faces crossing the coarse interface;
    coarse diagonal couplings (vertical, package, extra) = 2x2 sums.
    Interior fine faces cancel in the Galerkin product (they couple
    cells of the same aggregate), so they simply do not appear.

    ``rescale_lateral`` halves the lateral face sums afterwards.  The
    raw Galerkin product over-stiffens lateral coupling: summing the
    two crossing faces gives ``2 g`` where the true 2h discretization of
    the same sheet conductance (``k t``, scale-invariant in-plane) is
    ``g`` — the classic factor-2 defect of piecewise-constant
    aggregation in 2D.  Halving recovers the spec-built coarse-grid
    stencil exactly (vertical and package terms scale with cell AREA,
    so their 4x sums are already correct), which is what turns the
    V-cycle from a ~0.87/cycle crawl into a ~0.2/cycle solver
    (DESIGN.md §7.5).  ``build_levels`` applies it by default;
    ``tests/test_multigrid.py`` pins the raw product against the
    explicit ``R G P`` identity.
    """
    L, NY, NX = F["g_pkg"].shape
    if NY % 2 or NX % 2:
        raise ValueError(f"cannot 2x2-coarsen odd grid {NY}x{NX}")

    def sum4(x):                       # all four cells of the aggregate
        return x.reshape(L, NY // 2, 2, NX // 2, 2).sum(axis=(2, 4))

    def sum_rows(x):                   # row pairs at a fixed fine column
        return x.reshape(L, NY // 2, 2, x.shape[2]).sum(axis=2)

    def sum_cols(x):                   # column pairs at a fixed fine row
        return x.reshape(L, x.shape[1], NX // 2, 2).sum(axis=3)

    lat = 0.5 if rescale_lateral else 1.0
    Fc = {
        # left faces of the aggregate's left column (fine x = 2X)
        "gx_lf": lat * sum_rows(F["gx_lf"][:, :, 0::2]),
        # right faces of the right column (fine x = 2X + 1)
        "gx_rt": lat * sum_rows(F["gx_rt"][:, :, 1::2]),
        # top faces of the top row (fine y = 2Y)
        "gy_up": lat * sum_cols(F["gy_up"][:, 0::2, :]),
        # bottom faces of the bottom row (fine y = 2Y + 1)
        "gy_dn": lat * sum_cols(F["gy_dn"][:, 1::2, :]),
        "gz_up": sum4(F["gz_up"]),
        "gz_dn": sum4(F["gz_dn"]),
        "g_pkg": sum4(F["g_pkg"]),
    }
    return Fc, sum4(d_extra)


def restrict(r: jax.Array) -> jax.Array:
    """R = P^T: sum each 2x2 fine block into its coarse cell."""
    L, NY, NX = r.shape
    return r.reshape(L, NY // 2, 2, NX // 2, 2).sum(axis=(2, 4))


def prolong(e: jax.Array) -> jax.Array:
    """P: inject each coarse value into its 2x2 fine cells."""
    return jnp.repeat(jnp.repeat(e, 2, axis=1), 2, axis=2)


def build_levels(F: dict, d_extra, min_n: int = MIN_COARSE_N) -> list:
    """The hierarchy [(F_0, d_0), (F_1, d_1), ...], finest first.

    Every level is the rescaled Galerkin coarsening of the one above
    (see :func:`coarsen`), so every level stays a spec-built
    face-conductance stencil.  Coarsening stops when either in-plane
    dimension goes odd or drops below ``min_n``.  Shapes are static, so
    the list is built at trace time and the recursion over it unrolls
    into one jitted program.
    """
    d_extra = jnp.broadcast_to(jnp.asarray(d_extra, jnp.float32),
                               F["g_pkg"].shape)
    levels = [(F, d_extra)]
    while True:
        _, ny, nx = levels[-1][0]["g_pkg"].shape
        if ny % 2 or nx % 2 or min(ny, nx) // 2 < min_n:
            # hierarchy construction happens at trace time when called
            # from a jitted driver, so these count builds-per-compile
            obs.count("mg/hierarchies_built")
            obs.count(f"mg/hierarchies_built[levels={len(levels)}]")
            return levels
        levels.append(coarsen(*levels[-1], rescale_lateral=True))


# ---------------------------------------------------------------------------
# red-black z-line Gauss-Seidel smoother (jnp oracle; kernels/mg_smooth
# mirrors this exactly)
# ---------------------------------------------------------------------------

def line_solve(rhs: jax.Array, F: dict, d_extra) -> jax.Array:
    """Solve every (y, x) column's vertical tridiagonal system exactly.

    System per column:  diag[l] u[l] - gz_up[l] u[l-1] - gz_dn[l] u[l+1]
    = rhs[l]  — the operator restricted to the column with lateral
    neighbors frozen.  Void cells (all-zero rows over the margin ring)
    reduce to ``1 * u = 0``.  Thomas algorithm, unrolled over the small
    static layer count.
    """
    L = rhs.shape[0]
    d = diagonal(F, d_extra)
    d = jnp.where(d > 0, d, 1.0)
    lo = -F["gz_up"]            # coupling to layer l-1 (zero at l = 0)
    up = -F["gz_dn"]            # coupling to layer l+1 (zero at l = L-1)

    # forward elimination
    cp = [up[0] / d[0]]
    dp = [rhs[0] / d[0]]
    for l in range(1, L):
        denom = d[l] - lo[l] * cp[-1]
        denom = jnp.where(jnp.abs(denom) > 0, denom, 1.0)
        cp.append(up[l] / denom)
        dp.append((rhs[l] - lo[l] * dp[-1]) / denom)

    # back substitution
    u = [dp[-1]]
    for l in range(L - 2, -1, -1):
        u.append(dp[l] - cp[l] * u[-1])
    return jnp.stack(u[::-1], axis=0)


def _parity(ny: int, nx: int) -> jax.Array:
    yy = jax.lax.broadcasted_iota(jnp.int32, (ny, nx), 0)
    xx = jax.lax.broadcasted_iota(jnp.int32, (ny, nx), 1)
    return (yy + xx) % 2


def rb_line_sweep(T: jax.Array, b: jax.Array, F: dict, d_extra,
                  color: int) -> jax.Array:
    """One half-sweep: update the columns whose in-plane parity is
    ``color`` by their exact z-line solve, lateral neighbors frozen at
    the current iterate (their parity is ``1 - color``, so red->black is
    a true Gauss-Seidel ordering)."""
    t_lf = jnp.concatenate([T[:, :, :1], T[:, :, :-1]], axis=2)
    t_rt = jnp.concatenate([T[:, :, 1:], T[:, :, -1:]], axis=2)
    t_up = jnp.concatenate([T[:, :1], T[:, :-1]], axis=1)
    t_dn = jnp.concatenate([T[:, 1:], T[:, -1:]], axis=1)
    lateral = (F["gx_lf"] * t_lf + F["gx_rt"] * t_rt
               + F["gy_up"] * t_up + F["gy_dn"] * t_dn)
    u = line_solve(b + lateral, F, d_extra)
    mask = (_parity(T.shape[1], T.shape[2]) == color)[None]
    return jnp.where(mask, u, T)


def _smooth(T, b, F, d_extra, colors, sweep_fn):
    for c in colors:
        T = sweep_fn(T, b, F, d_extra, c)
    return T


# ---------------------------------------------------------------------------
# the symmetric V-cycle
# ---------------------------------------------------------------------------

def coarse_factorization(levels: list):
    """Dense Cholesky factorization of the coarsest-level operator.

    Relaxation alone cannot resolve the stack's near-null global mode
    (the whole grid couples to ambient only through the tiny package
    conductance, so the constant vector has an eigenvalue orders of
    magnitude below the rest) — a V-cycle whose coarsest level merely
    smooths stalls on exactly that mode.  The coarsest system is a few
    hundred unknowns, so we materialize it by applying the operator to
    the identity, symmetrically Jacobi-scale it for float32 conditioning,
    pin void rows to identity, and Cholesky-factor ONCE per hierarchy;
    every cycle then solves the coarsest level exactly (a symmetric
    operation, so the preconditioner property is preserved).  The factor
    and every solve with it run at ``HIGHEST`` matmul precision: the
    near-null mode is exactly what bf16 MXU operands would blur.
    """
    F, d_extra = levels[-1]
    L, ny, nx = F["g_pkg"].shape
    n = L * ny * nx
    eye = jnp.eye(n, dtype=jnp.float32)
    cols = jax.vmap(
        lambda v: operator(v.reshape(L, ny, nx), F, d_extra).ravel())(eye)
    A = cols.T
    d = jnp.diagonal(A)
    void = d <= 0
    A = A + jnp.diag(jnp.where(void, 1.0, 0.0))     # void cells: u = 0
    s = 1.0 / jnp.sqrt(jnp.where(void, 1.0, d))     # Jacobi scaling
    As = s[:, None] * A * s[None, :]
    with jax.default_matmul_precision("highest"):
        return jax.scipy.linalg.cho_factor(As), s


def coarse_solve_fn(levels: list):
    """Exact coarsest-level solve closure (see
    :func:`coarse_factorization`)."""
    cf, s = coarse_factorization(levels)
    shape = levels[-1][0]["g_pkg"].shape

    def solve(b):
        with jax.default_matmul_precision("highest"):
            y = jax.scipy.linalg.cho_solve(cf, s * b.ravel())
        return (s * y).reshape(shape)

    return solve


def v_cycle(levels: list, b: jax.Array, nu1: int = 1, nu2: int = 1,
            lvl: int = 0, sweep_fn=rb_line_sweep,
            prolong_fn=prolong, coarse_solve=None) -> jax.Array:
    """One V(nu1, nu2) cycle for ``A e = b`` from a zero initial guess.

    Pre-smoothing sweeps red->black, post-smoothing black->red, and the
    coarsest level is solved exactly (``coarse_solve``; falls back to a
    palindromic block of line sweeps when None) — so with the default
    injection prolongation (the restriction's transpose) the cycle, as a
    linear operator on ``b``, is symmetric positive definite and
    therefore a valid CG preconditioner (``mgcg_solve_fields``).
    """
    F, d_extra = levels[lvl]
    T = jnp.zeros_like(b)
    if lvl == len(levels) - 1:
        if coarse_solve is not None:
            return coarse_solve(b)
        for _ in range(N_COARSE_SWEEPS):
            T = _smooth(T, b, F, d_extra, (0, 1), sweep_fn)
        for _ in range(N_COARSE_SWEEPS):
            T = _smooth(T, b, F, d_extra, (1, 0), sweep_fn)
        return T
    for _ in range(nu1):
        T = _smooth(T, b, F, d_extra, (0, 1), sweep_fn)
    r = b - operator(T, F, d_extra)
    e = v_cycle(levels, restrict(r), nu1, nu2, lvl + 1, sweep_fn,
                prolong_fn, coarse_solve)
    T = T + prolong_fn(e)
    for _ in range(nu2):
        T = _smooth(T, b, F, d_extra, (1, 0), sweep_fn)
    return T


def _resolve_sweep(use_pallas: bool):
    if use_pallas:
        from repro.kernels.mg_smooth import ops as _ops
        return _ops.rb_line_sweep
    return rb_line_sweep


# ---------------------------------------------------------------------------
# solver drivers
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("max_cycles", "nu1", "nu2",
                                   "use_pallas"))
def mg_solve_fields(b: jax.Array, F: dict, d_extra=0.0, tol: float = 1e-8,
                    max_cycles: int = 200, nu1: int = 1, nu2: int = 1,
                    use_pallas: bool = False):
    """Stand-alone V-cycle iteration:  x += V(b - A x)  until the
    residual drops below ``tol * ||b||`` or stops contracting.  The
    TRUE residual is recomputed every cycle, so in float32 it floors
    near machine precision well above a 1e-8 relative target — the
    stagnation guard (< 10% reduction over a cycle) stops the loop at
    that floor instead of spinning to ``max_cycles``.  Returns
    ``(x, n_cycles)``."""
    sweep_fn = _resolve_sweep(use_pallas)
    levels = build_levels(F, d_extra)
    coarse = coarse_solve_fn(levels)
    Fd, dd = levels[0]
    bnorm = jnp.linalg.norm(b)

    def cond(state):
        _, r, it, prev = state
        res = jnp.linalg.norm(r)
        converged = res <= tol * bnorm
        stalled = (it >= 2) & (res > 0.9 * prev)
        # health guard: a non-finite residual means the cycle diverged —
        # every comparison above is False on NaN, so without this the
        # loop would spin NaN through all max_cycles before returning
        return ~(converged | stalled) & jnp.isfinite(res) & (it < max_cycles)

    def body(state):
        x, r, it, _ = state
        e = v_cycle(levels, r, nu1, nu2, sweep_fn=sweep_fn,
                    coarse_solve=coarse)
        x = x + e
        return (x, b - operator(x, Fd, dd), it + 1,
                jnp.linalg.norm(r))

    x, _, it, _ = jax.lax.while_loop(
        cond, body, (jnp.zeros_like(b), b, jnp.int32(0),
                     jnp.float32(jnp.inf)))
    return x, it


def iterate_fixed(levels: list, b: jax.Array, n_cycles: int,
                  nu1: int = 1, nu2: int = 1, sweep_fn=rb_line_sweep,
                  coarse_solve=None) -> jax.Array:
    """Fixed-cycle-count V-cycle iteration (``fori_loop``) on a
    pre-built hierarchy: uniform cost per call, so transient steps scan
    and sweep batches vmap — the MG counterpart of
    :func:`thermal.pcg_fixed`.  Build ``levels`` AND ``coarse_solve``
    once OUTSIDE any scan (``thermal.implicit_lhs_solver`` does) so the
    coarse operators and the coarsest factorization are constants of
    the compiled step."""
    Fd, dd = levels[0]

    def body(_, state):
        x, r = state
        e = v_cycle(levels, r, nu1, nu2, sweep_fn=sweep_fn,
                    coarse_solve=coarse_solve)
        x = x + e
        return x, r - operator(e, Fd, dd)

    x, _ = jax.lax.fori_loop(0, n_cycles, body, (jnp.zeros_like(b), b))
    return x


@partial(jax.jit, static_argnames=("n_cycles", "nu1", "nu2", "use_pallas"))
def mg_fixed(b: jax.Array, F: dict, d_extra=0.0, n_cycles: int = 3,
             nu1: int = 1, nu2: int = 1,
             use_pallas: bool = False) -> jax.Array:
    """Jitted convenience wrapper over :func:`iterate_fixed`."""
    levels = build_levels(F, d_extra)
    return iterate_fixed(levels, b, n_cycles, nu1, nu2,
                         _resolve_sweep(use_pallas),
                         coarse_solve_fn(levels))


@partial(jax.jit, static_argnames=("max_iter", "nu1", "nu2", "use_pallas"))
def mgcg_solve_fields(b: jax.Array, F: dict, d_extra=0.0, tol: float = 1e-8,
                      max_iter: int = 500, nu1: int = 1, nu2: int = 1,
                      use_pallas: bool = False):
    """V-cycle-preconditioned CG (the symmetric cycle is SPD, so plain
    PCG theory applies).  Returns ``(x, n_iterations)``."""
    from repro.core.thermal import pcg
    sweep_fn = _resolve_sweep(use_pallas)
    levels = build_levels(F, d_extra)
    coarse = coarse_solve_fn(levels)
    Fd, dd = levels[0]
    A = lambda v: operator(v, Fd, dd)
    Minv = lambda r: v_cycle(levels, r, nu1, nu2, sweep_fn=sweep_fn,
                             coarse_solve=coarse)
    return pcg(A, Minv, b, tol, max_iter)


__all__ = ["coarsen", "restrict", "prolong", "build_levels", "operator",
           "diagonal", "line_solve", "rb_line_sweep", "v_cycle",
           "coarse_factorization", "coarse_solve_fn", "iterate_fixed",
           "mg_solve_fields", "mg_fixed", "mgcg_solve_fields"]
