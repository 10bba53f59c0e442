"""HotSpot-equivalent 3D RC thermal model of the paper's die stack (Fig 9).

Stack (top -> bottom):  Si_4 | Si_3 | Si_2 | Si_1 | TIM | heat spreader |
heat sink -> convection to ambient.

Discretization: the four silicon layers AND the copper heat spreader are a
regular ny x nx grid over the die footprint (HotSpot's grid mode resolves
the spreader laterally too — essential: lateral spreading through ~1 mm of
copper is what flattens small hot dies; a lumped spreader misses it and
wildly overestimates both the peak and the span of the 2.3 mm SIMD die).
Below the spreader a lumped path models the sink:

    R_pkg = R_spread(spreader->sink) + R_cond(sink) + R_convec

applied as a uniform per-cell conductance to ambient.  Each layer has its
own lateral sheet conductance g_lat[l] = k_l * t_l and each interface its
own vertical conductance (die-bond between Si layers; TIM between Si_1 and
the spreader).

The steady-state system  G T = P  is SPD and solved matrix-free with
Jacobi-preconditioned CG; the stencil application is the Pallas kernel
``kernels/thermal_stencil`` (the jnp implementation here is the oracle).
Constants are ONE documented set used for both the AP and the SIMD dies
(DESIGN.md §7.2) so the comparison is apples-to-apples, as in the paper.

Heterogeneous stacks: every operator here is built from a declarative
``repro.stack.spec.StackSpec`` (ordered dies + interfaces, spreader last).
The legacy ``StackParams`` shorthand is converted through
``spec_from_params`` — ``PAPER_STACK`` is now just the named spec
``PAPER_SPEC`` and reproduces the pre-refactor numbers exactly; DRAM-on-
logic stacks come from ``repro.stack.spec.dram_on_logic``.
"""
from __future__ import annotations

import dataclasses
import math
from collections import OrderedDict
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.constants import AMBIENT_C
from repro.stack.spec import (PAPER_SPEC, PAPER_STACK, StackParams,
                              StackSpec, spec_from_params)

__all__ = [  # re-exports kept for callers of the pre-refactor module
    "AMBIENT_C", "PAPER_SPEC", "PAPER_STACK", "StackParams", "StackSpec",
    "spec_from_params", "Grid", "package_resistance", "steady_state",
    "steady_state_stats", "SOLVERS", "HEALTH_RTOL", "fallback_chain",
    "apply_operator", "apply_operator_fields", "pcg", "pcg_fixed",
    "transient", "transient_solve", "explicit_dt", "transient_implicit",
    "transient_implicit_fields", "transient_solve_implicit",
]

#: selectable linear-solver backends for the fields operator: Jacobi-PCG
#: (the original), stand-alone geometric multigrid V-cycles, and
#: V-cycle-preconditioned CG (see ``core/multigrid.py``, DESIGN.md §7.5)
SOLVERS = ("pcg", "mg", "mgcg")

#: TRUE-relative-residual bar for "this steady solve is healthy".
#: Deliberately loose: converged solves stop at the float32 residual
#: floor rather than their nominal tol, and that floor grows with the
#: grid (measured ~6e-3 for mgcg on the 256^2 shoot-out stack), so the
#: bar must sit well above it — yet orders of magnitude below any
#: diverged (non-finite) or genuinely stagnated solve, which is what
#: the fallback chain catches.
HEALTH_RTOL = 2e-2

#: grids whose conductance fields :meth:`Grid.fields` keeps on the device.
#: The sweep and serving paths make a new grid per job (``r_convec`` is
#: drawn per job), so the cache must be bounded; a steady map on a fixed
#: grid hits every time after its first solve.
FIELDS_CACHE_SIZE = 4
_FIELDS_CACHE: OrderedDict = OrderedDict()


def package_resistance(die_area_m2: float, p: StackParams = PAPER_STACK
                       ) -> float:
    """Lumped R from the spreader underside to ambient [K/W].

    Thin compatibility wrapper over
    :meth:`repro.stack.spec.StackSpec.package_resistance`.
    """
    return spec_from_params(p).package_resistance(die_area_m2)


# ---------------------------------------------------------------------------
# grid conductances (per layer / per interface)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Grid:
    die_w: float                # die edge [m] (square dies, as in the paper)
    ny: int                     # cells across the DIE footprint
    nx: int
    params: StackParams = PAPER_STACK
    pkg_area: float = 0.0       # area feeding the package lump [m^2];
    #   0 -> the spreader footprint (die + margin).  Sub-die zooms (one AP
    #   block under tiling symmetry) pass the FULL die area so each cell
    #   carries the same package conductance share as the die-level solve.
    margin: int = 0             # extra spreader-only cells per side: the
    #   copper plate extends beyond the die, so die edges couple to cooler
    #   outer spreader — the source of the paper's ~3C center-to-edge span.
    spec: StackSpec | None = None   # heterogeneous stack; None -> the
    #   homogeneous ``params`` expanded through ``spec_from_params``.

    @property
    def stack(self) -> StackSpec:
        """The StackSpec every operator on this grid is built from."""
        return self.spec if self.spec is not None \
            else spec_from_params(self.params)

    @property
    def n_layers(self) -> int:
        return self.stack.n_layers

    @property
    def n_die_layers(self) -> int:
        """Device layers (logic + DRAM) — everything above the spreader."""
        return self.stack.n_die_layers

    @property
    def cell_w(self) -> float:
        return self.die_w / self.nx

    @property
    def cell_area(self) -> float:
        return self.cell_w * (self.die_w / self.ny)

    @property
    def dom_ny(self) -> int:
        return self.ny + 2 * self.margin

    @property
    def dom_nx(self) -> int:
        return self.nx + 2 * self.margin

    def conductances(self) -> dict:
        """g_lat [L], g_vert [L-1] (interfaces, top->bottom), g_pkg scalar."""
        s = self.stack
        g_lat = s.lateral_conductances()
        g_vert = s.vertical_conductances(self.cell_area)
        dom_area = self.dom_ny * self.dom_nx * self.cell_area
        a_pkg = self.pkg_area or dom_area
        r_pkg = s.package_resistance(a_pkg)
        # per-cell share: cell_area / (r_pkg * A) — reduces to
        # 1/(r_pkg * ncells) when the grid covers the package source area
        g_pkg = self.cell_area / (r_pkg * a_pkg)
        return {"g_lat": jnp.asarray(g_lat, jnp.float32),
                "g_vert": jnp.asarray(g_vert, jnp.float32),
                "g_pkg": float(g_pkg), "r_pkg": float(r_pkg)}

    def fields(self) -> dict:
        """Per-face conductance fields over the (die + margin) domain.

        Die layers (logic and DRAM) exist only over the die footprint
        (faces outside it are zero = adiabatic); the spreader layer spans
        the full domain.  Returns seven [L, NY, NX] arrays: gx_lf, gx_rt,
        gy_up, gy_dn (lateral faces), gz_up, gz_dn (interfaces), g_pkg
        (bottom lump).

        The arrays are built once per (grid, default device) and kept on
        the device in an LRU of :data:`FIELDS_CACHE_SIZE` grids; a hit
        hands back the same arrays (``thermal/fields/cache_hit`` and
        ``thermal/fields/cache_miss`` count the lookups).
        """
        key = (self, jax.config.jax_default_device)
        F = _FIELDS_CACHE.get(key)
        if F is None:
            obs.count("thermal/fields/cache_miss")
            # concrete arrays even when called under a trace: a cached
            # tracer would leak out of the trace that made it
            with jax.ensure_compile_time_eval():
                F = self._build_fields()
            _FIELDS_CACHE[key] = F
            if len(_FIELDS_CACHE) > FIELDS_CACHE_SIZE:
                _FIELDS_CACHE.popitem(last=False)
        else:
            obs.count("thermal/fields/cache_hit")
            _FIELDS_CACHE.move_to_end(key)
        return dict(F)

    def _build_fields(self) -> dict:
        """:meth:`fields` built afresh, in numpy, and uploaded."""
        g = self.conductances()
        L = self.n_layers
        NY, NX, m = self.dom_ny, self.dom_nx, self.margin
        mask = np.zeros((L, NY, NX), np.float32)
        mask[:-1, m:m + self.ny, m:m + self.nx] = 1.0   # dies: footprint only
        mask[-1] = 1.0                                  # spreader: everywhere
        g_cell = np.asarray(g["g_lat"])[:, None, None] * mask

        def face(a, b):  # harmonic mean of cell conductances (0-safe)
            s = a + b
            return np.where(s > 0, 2 * a * b / np.maximum(s, 1e-30), 0.0)

        gx = face(g_cell[:, :, :-1], g_cell[:, :, 1:])   # [L, NY, NX-1]
        gy = face(g_cell[:, :-1, :], g_cell[:, 1:, :])   # [L, NY-1, NX]
        z = np.zeros((L, NY, 1), np.float32)
        gx_lf = np.concatenate([z, gx], axis=2)
        gx_rt = np.concatenate([gx, z], axis=2)
        zy = np.zeros((L, 1, NX), np.float32)
        gy_up = np.concatenate([zy, gy], axis=1)
        gy_dn = np.concatenate([gy, zy], axis=1)
        # vertical: interface exists where BOTH layers have material
        gv = np.asarray(g["g_vert"])[:, None, None] \
            * mask[:-1] * mask[1:]                       # [L-1, NY, NX]
        zl = np.zeros((1, NY, NX), np.float32)
        gz_up = np.concatenate([zl, gv], axis=0)
        gz_dn = np.concatenate([gv, zl], axis=0)
        g_pkg = np.zeros((L, NY, NX), np.float32)
        g_pkg[-1] = g["g_pkg"]
        return {k: jnp.asarray(v, jnp.float32) for k, v in dict(
            gx_lf=gx_lf, gx_rt=gx_rt, gy_up=gy_up, gy_dn=gy_dn,
            gz_up=gz_up, gz_dn=gz_dn, g_pkg=g_pkg).items()}

    def capacities(self) -> jax.Array:
        return jnp.asarray(self.stack.capacities(self.cell_area),
                           jnp.float32)

    def capacity_field(self) -> jax.Array:
        """Per-cell heat capacity [J/K] over the full domain, [L, NY, NX].

        Void cells (die layers over the margin ring) keep the die value:
        they have zero conductance and zero power, so they simply stay at
        their initial temperature; a nonzero capacity keeps the implicit
        system's diagonal well conditioned.
        """
        c = np.asarray(self.capacities())
        return jnp.asarray(
            np.broadcast_to(c[:, None, None],
                            (self.n_layers, self.dom_ny, self.dom_nx)),
            jnp.float32)

    def pad_power(self, power) -> jax.Array:
        """[n_die, ny, nx] die power -> [L, ny, nx] (spreader heatless)."""
        power = jnp.asarray(power, jnp.float32)
        if power.shape[0] == self.n_layers:
            return power
        pad = jnp.zeros((self.n_layers - power.shape[0],) +
                        power.shape[1:], jnp.float32)
        return jnp.concatenate([power, pad], axis=0)


# ---------------------------------------------------------------------------
# stencil operator (jnp reference; kernels/thermal_stencil mirrors this)
# ---------------------------------------------------------------------------

def _vectors(L: int, g_lat, g_vert, g_pkg):
    """Normalize scalar-or-vector conductances to per-layer vectors."""
    g_lat = jnp.broadcast_to(jnp.asarray(g_lat, jnp.float32), (L,))
    g_vert = jnp.broadcast_to(jnp.asarray(g_vert, jnp.float32),
                              (max(L - 1, 1),))[: L - 1]
    gv_u = jnp.concatenate([jnp.zeros((1,), jnp.float32), g_vert])
    gv_d = jnp.concatenate([g_vert, jnp.zeros((1,), jnp.float32)])
    g_pkg_vec = jnp.zeros((L,), jnp.float32).at[-1].set(g_pkg)
    return g_lat, gv_u, gv_d, g_pkg_vec


def apply_operator(T: jax.Array, g_lat, g_vert, g_pkg) -> jax.Array:
    """y = G @ T.  T: [L, ny, nx] (layer 0 = TOP die, layer L-1 = spreader).

    g_lat: scalar or [L]; g_vert: scalar or [L-1]; g_pkg: scalar (bottom
    layer to ambient).  Adiabatic side/top boundaries.
    """
    L = T.shape[0]
    g_lat, gv_u, gv_d, g_pkg_vec = _vectors(L, g_lat, g_vert, g_pkg)
    gl = g_lat[:, None, None]
    t_up = jnp.concatenate([T[:, :1], T[:, :-1]], axis=1)
    t_dn = jnp.concatenate([T[:, 1:], T[:, -1:]], axis=1)
    t_lf = jnp.concatenate([T[:, :, :1], T[:, :, :-1]], axis=2)
    t_rt = jnp.concatenate([T[:, :, 1:], T[:, :, -1:]], axis=2)
    y = gl * (4.0 * T - t_up - t_dn - t_lf - t_rt)
    l_up = jnp.concatenate([T[:1], T[:-1]], axis=0)
    l_dn = jnp.concatenate([T[1:], T[-1:]], axis=0)
    y = y + gv_u[:, None, None] * (T - l_up) \
          + gv_d[:, None, None] * (T - l_dn) \
          + g_pkg_vec[:, None, None] * T
    return y


def _diag(shape, g_lat, g_vert, g_pkg):
    """Diagonal of G (for Jacobi preconditioning)."""
    L, ny, nx = shape
    g_lat, gv_u, gv_d, g_pkg_vec = _vectors(L, g_lat, g_vert, g_pkg)
    d = jnp.broadcast_to((4.0 * g_lat)[:, None, None], shape)
    edge_y = jnp.zeros((ny, 1)).at[0].set(1).at[-1].set(1)
    edge_x = jnp.zeros((1, nx)).at[:, 0].set(1).at[:, -1].set(1)
    d = d - g_lat[:, None, None] * (edge_y + edge_x)[None]
    d = d + (gv_u + gv_d + g_pkg_vec)[:, None, None]
    return d


# ---------------------------------------------------------------------------
# generic preconditioned CG (shared by every solver in this repo: the jnp and
# Pallas steady-state paths, and the implicit transient steppers below)
# ---------------------------------------------------------------------------

def _as_precond(Minv):
    """Normalize a preconditioner to a closure: an inverse-diagonal
    array (Jacobi) or a callable (e.g. one multigrid V-cycle)."""
    return Minv if callable(Minv) else (lambda r: Minv * r)


def _dot(a, b):
    """f32-exact inner product.  XLA's default precision for an f32 dot
    on a TPU feeds the MXU bf16 operands; HIGHEST keeps all 24 mantissa
    bits there, and is what every CPU dot already does."""
    return jnp.vdot(a, b, precision=jax.lax.Precision.HIGHEST)


def pcg(A, Minv, b, tol=1e-8, max_iter=6000):
    """Preconditioned CG for the SPD system A x = b.

    ``A`` is a matvec closure; ``Minv`` is either the inverse diagonal
    (array, Jacobi) or a callable applying any fixed SPD preconditioner
    (``multigrid.v_cycle``).  Tolerance-based ``while_loop`` termination;
    see :func:`pcg_fixed` for the fixed-cost variant used inside
    vmapped/scanned transient stepping.  Returns ``(x, n_iterations)``.
    """
    apply_Minv = _as_precond(Minv)
    x = jnp.zeros_like(b)
    r = b
    z = apply_Minv(r)
    p = z
    rz = _dot(r, z)
    bnorm = jnp.linalg.norm(b)

    def cond(state):
        x, r, p, rz, it = state
        return (jnp.linalg.norm(r) > tol * bnorm) & (it < max_iter)

    def body(state):
        x, r, p, rz, it = state
        Ap = A(p)
        alpha = rz / _dot(p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        z = apply_Minv(r)
        rz_new = _dot(r, z)
        beta = rz_new / rz
        p = z + beta * p
        return x, r, p, rz_new, it + 1

    x, r, p, rz, it = jax.lax.while_loop(
        cond, body, (x, r, p, rz, jnp.int32(0)))
    return x, it


def pcg_fixed(A, Minv, b, n_iter: int):
    """PCG with a fixed iteration count (``fori_loop``).

    Uniform cost per call, so a batch of solves vmaps without masking and a
    scan over time steps stays one compiled program.  Guarded against a zero
    right-hand side (alpha would be 0/0): the update is suppressed when the
    residual has already vanished.
    """
    apply_Minv = _as_precond(Minv)
    x = jnp.zeros_like(b)
    r = b
    z = apply_Minv(r)
    p = z
    rz = _dot(r, z)

    def body(_, state):
        x, r, p, rz = state
        Ap = A(p)
        pAp = _dot(p, Ap)
        ok = pAp > 0.0
        alpha = jnp.where(ok, rz / jnp.where(ok, pAp, 1.0), 0.0)
        x = x + alpha * p
        r = r - alpha * Ap
        z = apply_Minv(r)
        rz_new = _dot(r, z)
        beta = jnp.where(ok, rz_new / jnp.where(rz > 0, rz, 1.0), 0.0)
        p = z + beta * p
        return x, r, p, rz_new

    x, *_ = jax.lax.fori_loop(0, n_iter, body, (x, r, p, rz))
    return x


@partial(jax.jit, static_argnames=("max_iter",))
def _cg_solve(b, diag, g_lat, g_vert, g_pkg, tol=1e-8, max_iter=6000):
    """Jacobi-preconditioned conjugate gradient for G T = b."""
    A = lambda v: apply_operator(v, g_lat, g_vert, g_pkg)
    return pcg(A, 1.0 / diag, b, tol, max_iter)[0]


# ---------------------------------------------------------------------------
# heterogeneous (face-conductance-field) operator — the production solver
# ---------------------------------------------------------------------------

def apply_operator_fields(T: jax.Array, F: dict) -> jax.Array:
    """y = G @ T with per-face conductances (zero faces = adiabatic)."""
    t_lf = jnp.concatenate([T[:, :, :1], T[:, :, :-1]], axis=2)
    t_rt = jnp.concatenate([T[:, :, 1:], T[:, :, -1:]], axis=2)
    t_up = jnp.concatenate([T[:, :1], T[:, :-1]], axis=1)
    t_dn = jnp.concatenate([T[:, 1:], T[:, -1:]], axis=1)
    l_up = jnp.concatenate([T[:1], T[:-1]], axis=0)
    l_dn = jnp.concatenate([T[1:], T[-1:]], axis=0)
    return (F["gx_lf"] * (T - t_lf) + F["gx_rt"] * (T - t_rt)
            + F["gy_up"] * (T - t_up) + F["gy_dn"] * (T - t_dn)
            + F["gz_up"] * (T - l_up) + F["gz_dn"] * (T - l_dn)
            + F["g_pkg"] * T)


def _diag_fields(F: dict) -> jax.Array:
    d = (F["gx_lf"] + F["gx_rt"] + F["gy_up"] + F["gy_dn"]
         + F["gz_up"] + F["gz_dn"] + F["g_pkg"])
    return jnp.where(d > 0, d, 1.0)     # void cells: identity rows


@partial(jax.jit, static_argnames=("max_iter",))
def _cg_solve_fields_stats(b, F, tol=1e-8, max_iter=8000):
    A = lambda v: apply_operator_fields(v, F)
    return pcg(A, 1.0 / _diag_fields(F), b, tol, max_iter)


def _cg_solve_fields(b, F, tol=1e-8, max_iter=8000):
    return _cg_solve_fields_stats(b, F, tol, max_iter)[0]


def _solve_fields(b, F, solver: str, use_pallas: bool, tol: float = 1e-8):
    """Route one fields solve ``G dT = b`` to the selected backend.

    Returns ``(dT, n_iterations)`` — CG iterations or V-cycles.  With
    ``use_pallas`` the PCG backend runs the Pallas stencil matvec and
    the multigrid backends run the Pallas red-black line smoother
    (``kernels/mg_smooth``).

    PCG gets one step of iterative refinement when its result misses
    :data:`HEALTH_RTOL`: in float32 the recursive residual CG stops on
    drifts away from the true one, which stalls far above it on large
    grids (9e-2 relative at the 384^2 shoot-out field, where multigrid
    reaches 2.5e-3).  CG on the true residual brings the sum to the
    float32 floor; a result already within the bar is returned as is.
    """
    from repro.core import multigrid
    if solver == "mg":
        return multigrid.mg_solve_fields(b, F, 0.0, tol,
                                         use_pallas=use_pallas)
    if solver == "mgcg":
        return multigrid.mgcg_solve_fields(b, F, 0.0, tol,
                                           use_pallas=use_pallas)
    if solver != "pcg":
        raise ValueError(f"unknown solver {solver!r}; expected {SOLVERS}")
    if use_pallas:
        from repro.kernels.thermal_stencil import ops as _ops
        cg = _ops.cg_solve_fields_stats
    else:
        cg = _cg_solve_fields_stats
    x, iters = cg(b, F, tol)
    r = b - apply_operator_fields(x, F)
    if not jnp.linalg.norm(r) <= HEALTH_RTOL * jnp.linalg.norm(b):
        dx, more = cg(r, F, tol)
        x, iters = x + dx, iters + more
    return x, iters


def fallback_chain(solver: str) -> tuple[tuple[str, float], ...]:
    """Attempt list for one guarded fields solve: (backend, tol scale).

    Starts at the requested backend, continues down the remaining of
    the ``mg -> mgcg -> pcg`` ladder (each rung trades speed for
    robustness), and always ends with a tightened-tolerance Jacobi-PCG
    — the slowest but most unconditionally dependable backend here.
    """
    order = ("mg", "mgcg", "pcg")
    if solver not in order:
        raise ValueError(f"unknown solver {solver!r}; expected {SOLVERS}")
    tail = order[order.index(solver):]
    return tuple((s, 1.0) for s in tail) + (("pcg", 0.1),)


@partial(jax.jit, static_argnames=("n_layers", "margin"))
def _steady_rhs(power, n_layers: int, margin: int):
    """Die power [n_die, ny, nx] -> the solve's right-hand side [L, NY, NX]
    (spreader and margin ring heatless), whether it is all finite, and
    its norm."""
    b = jnp.pad(power, ((0, n_layers - power.shape[0]),
                        (margin, margin), (margin, margin)))
    return b, jnp.isfinite(b).all(), jnp.linalg.norm(b)


@partial(jax.jit, static_argnames=("window",))
def _attempt_health(b, dT, F, finite, bnorm, iters, t_amb, window):
    """One attempt's die temperatures and health, in one program.

    ``window`` is ``(n_die, margin, ny, nx)``.  Returns ``(T, health)``:
    ``T`` the die window of ``dT`` plus ``t_amb``, and ``health`` the
    float32 vector ``[finite, ||b||, ||b - G dT|| / ||b||, iterations]``
    the host fetches in one transfer.
    """
    n_die, m, ny, nx = window
    rel = jnp.linalg.norm(b - apply_operator_fields(dT, F)) / bnorm
    health = jnp.stack([finite.astype(jnp.float32), bnorm, rel,
                        jnp.asarray(iters, jnp.float32)])
    return dT[:n_die, m:m + ny, m:m + nx] + t_amb, health


def _solve_fields_guarded(rhs, F, solver: str, use_pallas: bool,
                          tol: float, window: tuple, t_amb: float):
    """:func:`_solve_fields` hardened by health checks + fallback.

    ``rhs`` is :func:`_steady_rhs`'s ``(b, finite, ||b||)``.  Each
    attempt dispatches its solve and :func:`_attempt_health`, then
    fetches the health vector in one transfer (``sync/health``).  A
    non-finite ``b`` raises ``ValueError``; a zero ``||b||`` (x = 0 is
    exact) or a non-finite one (no backend can fix it) returns the
    ambient after one attempt.  A non-finite or ``> HEALTH_RTOL`` TRUE
    relative residual ``||b - G x||/||b||`` (a diverged or stagnated
    solve — or a backend forced down by
    ``repro.faults.inject.poison_solver``) advances to the next rung of
    :func:`fallback_chain`.  Returns ``(T_die, iterations, stats)`` with
    ``stats = {"attempts", "solved_by", "rel_residual"}``; retries are
    counted in ``obs`` under ``thermal/fallback/*``.
    """
    from repro.faults import inject
    b, finite, bnorm = rhs
    last = None
    for i, (s, scale) in enumerate(fallback_chain(solver)):
        with obs.span("thermal/steady/solve", solver=s, attempt=i + 1):
            if inject.solver_poisoned(s):
                dT, iters = jnp.full_like(b, jnp.nan), 0
            else:
                dT, iters = _solve_fields(b, F, s, use_pallas, tol * scale)
        with obs.span("thermal/steady/check"):
            T, health = _attempt_health(b, dT, F, finite, bnorm, iters,
                                        t_amb, window=window)
            with obs.span("sync/health"):
                b_finite, b_norm, resid, n_iter = np.asarray(health).tolist()
        # b's finiteness and norm come back with every attempt; the first
        # attempt's fetch settles both
        if not b_finite:
            raise ValueError(
                "steady_state: power map has non-finite cells; "
                "refusing to solve — NaN temperatures would silently "
                "poison every downstream verdict")
        if b_norm == 0.0 or not math.isfinite(b_norm):
            resid = 0.0 if b_norm == 0.0 else math.inf
            return jnp.full_like(T, t_amb), 0, {
                "attempts": 1, "solved_by": solver, "rel_residual": resid}
        last = (T, int(n_iter), {"attempts": i + 1, "solved_by": s,
                                 "rel_residual": resid})
        if math.isfinite(resid) and resid <= HEALTH_RTOL:
            if i:
                obs.count("thermal/fallback/recovered")
            return last
        if i == 0:
            obs.count("thermal/fallback/engaged")
        obs.count("thermal/fallback/retries")
        obs.count(f"thermal/fallback/unhealthy[{s}]")
    obs.count("thermal/fallback/exhausted")
    return last


def steady_state_stats(power: np.ndarray | jax.Array, grid: Grid,
                       t_amb: float = AMBIENT_C, use_pallas: bool = False,
                       solver: str = "pcg", tol: float = 1e-8
                       ) -> tuple[jax.Array, dict]:
    """:func:`steady_state` plus solver statistics.

    Returns ``(T_die, stats)`` with ``stats = {"iterations", "solver",
    "rel_residual", "attempts", "solved_by"}``: ``iterations`` counts
    CG iterations (pcg/mgcg) or V-cycles (mg), and ``rel_residual`` is
    the TRUE relative residual ``||b - G x|| / ||b||`` recomputed after
    the solve — the honest convergence signal (the mg backend in
    particular stops at the float32 residual floor rather than the
    nominal ``tol``, and a pathological hierarchy could stall earlier).
    An unhealthy solve (non-finite or ``> HEALTH_RTOL`` residual)
    automatically retries down :func:`fallback_chain`; ``attempts`` and
    ``solved_by`` record how far it had to go (``solver`` stays the
    REQUESTED backend).  Non-finite power maps raise ``ValueError``
    before anything is returned.

    Each attempt runs device programs only — the padding, the solver's
    own program and the health check — on the cached
    :meth:`Grid.fields`, and blocks once, on its health vector.
    """
    with obs.span("thermal/steady", solver=solver,
                  shape=f"{grid.n_layers}x{grid.dom_ny}x{grid.dom_nx}"):
        with obs.span("thermal/steady/fields"):
            F = grid.fields()
        with obs.span("thermal/steady/rhs"):
            rhs = _steady_rhs(jnp.asarray(power, jnp.float32),
                              grid.n_layers, grid.margin)
        window = (grid.n_die_layers, grid.margin, grid.ny, grid.nx)
        T, iters, fstats = _solve_fields_guarded(rhs, F, solver, use_pallas,
                                                 tol, window, t_amb)
        stats = {"iterations": iters, "solver": solver,
                 "rel_residual": fstats["rel_residual"],
                 "attempts": fstats["attempts"],
                 "solved_by": fstats["solved_by"]}
    return T, stats


def steady_state(power: np.ndarray | jax.Array, grid: Grid,
                 t_amb: float = AMBIENT_C, use_pallas: bool = False,
                 solver: str = "pcg") -> jax.Array:
    """Steady-state temperatures [C] of the DIE layers over the DIE.

    power: [n_die_layers, ny, nx] watts per cell of the die footprint (the
    spreader layer and margin ring are handled internally and stripped).
    ``solver`` selects the linear backend (:data:`SOLVERS`): Jacobi-PCG,
    stand-alone multigrid V-cycles, or V-cycle-preconditioned CG.
    """
    T, _ = steady_state_stats(power, grid, t_amb, use_pallas, solver)
    return T


@partial(jax.jit, static_argnames=("n_steps",))
def transient(T0, power, g_lat, g_vert, g_pkg, cap, dt, n_steps: int,
              t_amb: float = AMBIENT_C):
    """Explicit transient:  C dT/dt = P - G (T - Tamb).  Returns T(t_end)."""

    def step(T, _):
        dT = T - t_amb
        dTdt = (power - apply_operator(dT, g_lat, g_vert, g_pkg)) \
            / cap[:, None, None]
        return T + dt * dTdt, jnp.max(T)

    T, peaks = jax.lax.scan(step, T0, None, length=n_steps)
    return T, peaks


def transient_solve(power, grid: Grid, t_end: float,
                    t_amb: float = AMBIENT_C) -> tuple[jax.Array, jax.Array]:
    """Convenience wrapper: start from ambient, integrate to t_end seconds."""
    g = grid.conductances()
    cap = grid.capacities()
    power = grid.pad_power(power)
    dt = explicit_dt(grid)
    n = max(int(t_end / dt), 1)
    T0 = jnp.full(power.shape, t_amb, jnp.float32)
    return transient(T0, power, g["g_lat"], g["g_vert"], g["g_pkg"],
                     cap, dt, n, t_amb)


def explicit_dt(grid: Grid) -> float:
    """The explicit scheme's stability-bound time step (0.5x CFL margin)."""
    g = grid.conductances()
    cap = grid.capacities()
    gmax = float(4 * jnp.max(g["g_lat"]) + 2 * jnp.max(g["g_vert"])
                 + g["g_pkg"])
    return 0.5 * float(jnp.min(cap)) / gmax


# ---------------------------------------------------------------------------
# implicit (theta-scheme) transient: unconditionally stable, so the step size
# is set by accuracy, not the explicit CFL bound — the co-simulation engine's
# stepper (cosim.py replays per-interval power traces through it)
# ---------------------------------------------------------------------------

def _implicit_scan(dT0, power, A, solve, n_steps: int, lhs=None):
    """theta-scheme steps in excess-temperature space  C dT/dt = P - G dT.

    Solves for the increment:  (C/dt + theta G) delta = P - G dT_n,  then
    dT_{n+1} = dT_n + delta  (exact for any theta; backward Euler theta=1,
    Crank-Nicolson theta=0.5).  The LHS is SPD; ``solve`` is a fixed-cost
    closure for it (fixed-iteration PCG or fixed-cycle multigrid,
    :func:`implicit_lhs_solver`) so the whole integration is one scan —
    scannable and vmappable.

    With ``lhs`` (the theta-scheme LHS closure) given, the per-step ys
    also carry the TRUE relative linear residual of each inner solve,
    ``||rhs - lhs(delta)|| / ||rhs||`` — one extra matvec per step, paid
    only by callers that ask for the residuals (``with_residuals``),
    never in the default compiled program.
    """

    def step(dTc, _):
        rhs = power - A(dTc)
        delta = solve(rhs)
        # emit the PRE-step max, matching the explicit transient()'s peaks
        peak = jnp.max(dTc)
        if lhs is not None:
            res = jnp.linalg.norm(rhs - lhs(delta)) \
                / jnp.maximum(jnp.linalg.norm(rhs), 1e-30)
            return dTc + delta, (peak, res)
        return dTc + delta, peak

    return jax.lax.scan(step, dT0, None, length=n_steps)


def implicit_lhs_solver(A, F, cap3, dt, theta, *, solver: str = "pcg",
                        n_cg: int = 50, n_mg: int = 3,
                        use_pallas: bool = False):
    """Fixed-cost solve closure for the theta-scheme LHS
    ``(C/dt + theta G) delta = rhs`` over the fields operator.

    "pcg": ``n_cg`` Jacobi-PCG iterations on the closure ``A`` (which may
    be the Pallas stencil).  "mg": ``n_mg`` V-cycles on the Galerkin
    hierarchy of the theta-scaled fields — built ONCE here, outside any
    scan, so coarse operators are constants of the compiled step.
    """
    lhs = lambda v: cap3 / dt * v + theta * A(v)
    if solver == "mg":
        from repro.core import multigrid
        F_lhs = {k: theta * v for k, v in F.items()}
        levels = multigrid.build_levels(F_lhs, cap3 / dt)
        sweep_fn = multigrid._resolve_sweep(use_pallas)
        coarse = multigrid.coarse_solve_fn(levels)
        return lambda rhs: multigrid.iterate_fixed(
            levels, rhs, n_mg, sweep_fn=sweep_fn, coarse_solve=coarse)
    if solver != "pcg":
        raise ValueError(f"unknown solver {solver!r}; expected "
                         f"('pcg', 'mg')")
    Minv = 1.0 / (cap3 / dt + theta * _diag_fields(F))
    return lambda rhs: pcg_fixed(lhs, Minv, rhs, n_cg)


@partial(jax.jit, static_argnames=("n_steps", "n_cg", "with_residuals"))
def transient_implicit(T0, power, g_lat, g_vert, g_pkg, cap, dt,
                       n_steps: int, theta: float = 1.0,
                       t_amb: float = AMBIENT_C, n_cg: int = 50,
                       with_residuals: bool = False):
    """Implicit counterpart of :func:`transient` (same contract/returns).

    ``with_residuals=True`` (static) appends per-step relative linear
    residuals to the return — ``(T, peaks, res)`` — for telemetry; the
    default keeps the historical 2-tuple and compiled program.
    """
    L = T0.shape[0]
    diag = _diag(T0.shape, g_lat, g_vert, g_pkg)
    cap3 = jnp.broadcast_to(jnp.asarray(cap, jnp.float32), (L,))[:, None, None]
    A = lambda v: apply_operator(v, g_lat, g_vert, g_pkg)
    lhs = lambda v: cap3 / dt * v + theta * A(v)
    Minv = 1.0 / (cap3 / dt + theta * diag)
    solve = lambda rhs: pcg_fixed(lhs, Minv, rhs, n_cg)
    if with_residuals:
        dT, (peaks, res) = _implicit_scan(T0 - t_amb, power, A, solve,
                                          n_steps, lhs=lhs)
        return dT + t_amb, peaks + t_amb, res
    dT, peaks = _implicit_scan(T0 - t_amb, power, A, solve, n_steps)
    return dT + t_amb, peaks + t_amb


@partial(jax.jit, static_argnames=("n_steps", "n_cg", "solver", "n_mg",
                                   "use_pallas", "with_residuals"))
def transient_implicit_fields(T0, power, F: dict, cap3, dt, n_steps: int,
                              theta: float = 1.0, t_amb: float = AMBIENT_C,
                              n_cg: int = 50, solver: str = "pcg",
                              n_mg: int = 3, use_pallas: bool = False,
                              with_residuals: bool = False):
    """Implicit theta-scheme on the heterogeneous (production) operator.

    T0/power: [L, NY, NX] over the full (die + margin) domain; cap3 the
    per-cell capacity field (``Grid.capacity_field()``).  ``solver``
    selects the fixed-cost inner solve: ``n_cg`` PCG iterations or
    ``n_mg`` multigrid V-cycles per step.  ``with_residuals=True``
    (static) appends per-step relative linear residuals:
    ``(T, peaks, res)``.
    """
    A = lambda v: apply_operator_fields(v, F)
    solve = implicit_lhs_solver(A, F, cap3, dt, theta, solver=solver,
                                n_cg=n_cg, n_mg=n_mg,
                                use_pallas=use_pallas)
    if with_residuals:
        lhs = lambda v: cap3 / dt * v + theta * A(v)
        dT, (peaks, res) = _implicit_scan(T0 - t_amb, power, A, solve,
                                          n_steps, lhs=lhs)
        return dT + t_amb, peaks + t_amb, res
    dT, peaks = _implicit_scan(T0 - t_amb, power, A, solve, n_steps)
    return dT + t_amb, peaks + t_amb


def transient_solve_implicit(power, grid: Grid, t_end: float,
                             n_steps: int, theta: float = 1.0,
                             t_amb: float = AMBIENT_C, n_cg: int = 50,
                             solver: str = "pcg", n_mg: int = 3,
                             with_residuals: bool = False):
    """Implicit counterpart of :func:`transient_solve` with a chosen step
    count (the point: n_steps can be 10-1000x below the explicit bound).
    ``solver="mg"`` runs the multigrid inner solve on the fields form of
    the same stack.

    Returns ``(T, peaks)``.  ``with_residuals=True`` also computes the
    per-step relative inner-solve residuals on device (one extra matvec
    per step, another compiled program), returns ``(T, peaks, res)``
    and records them under ``thermal/transient/*`` when ``obs`` is
    enabled.
    """
    power = grid.pad_power(power)
    dt = t_end / n_steps
    T0 = jnp.full(power.shape, t_amb, jnp.float32)
    with obs.span("thermal/transient", solver=solver, n_steps=n_steps):
        if solver == "mg":
            F = grid.fields()
            cap3 = grid.capacity_field()
            out = transient_implicit_fields(T0, power, F, cap3, dt,
                                            n_steps, theta, t_amb, n_cg,
                                            solver="mg", n_mg=n_mg,
                                            with_residuals=with_residuals)
        else:
            g = grid.conductances()
            cap = grid.capacities()
            out = transient_implicit(T0, power, g["g_lat"], g["g_vert"],
                                     g["g_pkg"], cap, dt, n_steps, theta,
                                     t_amb, n_cg,
                                     with_residuals=with_residuals)
    if with_residuals and obs.is_enabled():
        obs.count("thermal/transient/solves")
        obs.count("thermal/transient/steps", n_steps)
        obs.count("thermal/transient/inner_iterations",
                  n_steps * (n_mg if solver == "mg" else n_cg))
        obs.observe_many("thermal/transient/step_rel_residual",
                         np.asarray(out[2], np.float64))
    return out
