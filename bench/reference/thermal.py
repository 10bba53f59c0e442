"""Plain reference for a steady thermal map of a die stack.

Imports nothing of the program.  It assembles the conductance operator
itself from the configuration's numbers (layers, interfaces, package)
and the grid (die cells per side, spreader margin), and solves G T = P
for the temperature rise over ambient:

- die layers lie only over the die footprint, the spreader over die and
  margin; a side with no neighbouring material is adiabatic;
- a lateral face between two cells of one layer conducts k * t;
- an interface conducts cell_area / (t_a / 2k_a + r + t_b / 2k_b);
- the spreader loses heat to ambient through the package resistance
  (Lee, Song and Au's closed form for spreading in the sink base, plus
  sink conduction and convection), shared evenly by its cells.

The solve is iterative refinement: the residual of every round in float64
on the host, each correction by Jacobi-preconditioned conjugate gradients
in float32 on the default device, until the float64 residual is below
1e-12 of the right-hand side.  The control runs the same rounds with
residual and correction in a lower precision on the device.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

RTOL = 1e-12
MAX_ROUNDS = 12
CONTROL_ROUNDS = 6
CG_TOL = 1e-6
CG_MAX_ITER = 8000


def spreading_resistance(a_source: float, a_plate: float, t: float, k: float,
                         h: float) -> float:
    """Lee, Song and Au (1995): constriction and spreading resistance of a
    source of area ``a_source`` on a plate of area ``a_plate``, thickness
    ``t``, conductivity ``k``, cooled by ``h`` on its far side [K/W]."""
    r1 = math.sqrt(a_source / math.pi)
    r2 = math.sqrt(a_plate / math.pi)
    eps, tau, bi = r1 / r2, t / r2, h * r2 / k
    lam = math.pi + 1.0 / (math.sqrt(math.pi) * eps)
    phi = ((math.tanh(lam * tau) + lam / bi)
           / (1.0 + lam / bi * math.tanh(lam * tau)))
    psi = (eps * tau + (1.0 - eps) * phi) / math.sqrt(math.pi)
    return psi / (k * r1 * math.sqrt(math.pi))


def package_resistance(config: dict, source_area: float,
                       r_convec: float | None = None) -> float:
    pk = config["package"]
    r_convec = pk["r_convec_K_W"] if r_convec is None else r_convec
    spreader = config["layers"][-1]
    a_sink = pk["sink_w_m"] ** 2
    h = 1.0 / (r_convec * a_sink)
    src_w = min(math.sqrt(source_area)
                + 2 * pk["spread_beta"] * spreader["t_m"], pk["spreader_w_m"])
    r_sp = spreading_resistance(src_w ** 2, a_sink, pk["t_sink_m"],
                                pk["k_sink_W_mK"], h)
    return r_sp + pk["t_sink_m"] / (pk["k_sink_W_mK"] * a_sink) + r_convec


class Operator:
    """G over die layers D [Ld, n, n] and the spreader S [N, N], N = n + 2m,
    for a die ``die_w_m`` wide; ``r_convec`` replaces the configuration's
    convection resistance where given."""

    def __init__(self, config: dict, n: int, margin: int, die_w_m: float,
                 r_convec: float | None = None):
        layers = config["layers"]
        if layers[-1]["kind"] != "spreader":
            raise ValueError("the last layer must be the spreader")
        self.n, self.m, self.N = n, margin, n + 2 * margin
        self.Ld = len(layers) - 1
        cell = die_w_m / n
        area = cell * cell
        self.cap = [l["c_J_m3K"] * l["t_m"] * area for l in layers]
        self.g_lat = [l["k_W_mK"] * l["t_m"] for l in layers]
        self.g_vert = [
            area / (0.5 * a["t_m"] / a["k_W_mK"] + r + 0.5 * b["t_m"] / b["k_W_mK"])
            for a, b, r in zip(layers[:-1], layers[1:],
                               config["interfaces_m2K_W"])]
        a_pkg = (self.N * cell) ** 2
        self.g_pkg = area / (package_resistance(config, a_pkg, r_convec)
                             * a_pkg)
        self._key = (n, margin, tuple(self.g_lat), tuple(self.g_vert),
                     self.g_pkg)

    def __hash__(self):         # a static argument of the jitted solve
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Operator) and self._key == other._key

    @staticmethod
    def _lap(X, xp, axes):
        """Neumann Laplacian sum_nb (X - X_nb) over ``axes``."""
        y = 0
        for ax in axes:
            d = xp.diff(X, axis=ax)
            shape = list(X.shape)
            shape[ax] = 1
            z = xp.zeros(shape, X.dtype)
            y = y + xp.concatenate([z, d], axis=ax) \
                - xp.concatenate([d, z], axis=ax)
        return y

    def apply(self, D, S, xp=np):
        """(G x) for x = (D, S); works on numpy or jax.numpy arrays."""
        dt = D.dtype
        m, n, Ld = self.m, self.n, self.Ld
        gl = xp.asarray(self.g_lat[:Ld], dt)[:, None, None]
        yD = gl * self._lap(D, xp, (1, 2))
        yS = xp.asarray(self.g_lat[-1], dt) * self._lap(S, xp, (0, 1)) \
            + xp.asarray(self.g_pkg, dt) * S
        if Ld > 1:
            gv = xp.asarray(self.g_vert[:Ld - 1], dt)[:, None, None]
            flux = gv * (D[1:] - D[:-1])
            z = xp.zeros((1, n, n), dt)
            yD = yD - xp.concatenate([flux, z]) + xp.concatenate([z, flux])
        g = xp.asarray(self.g_vert[Ld - 1], dt)
        bond = g * (D[-1] - S[m:m + n, m:m + n])
        yD = yD + xp.concatenate([xp.zeros((Ld - 1, n, n), dt), bond[None]])
        yS = yS - xp.pad(bond, ((m, m), (m, m)))
        return yD, yS

    def diagonal(self):
        """float64 diagonals of G: (dD [Ld, n, n], dS [N, N])."""
        def neighbours(k):
            c = np.full(k, 2.0)
            c[0] = c[-1] = 1.0
            return c[:, None] + c[None, :]
        nbD, nbS = neighbours(self.n), neighbours(self.N)
        dD = np.stack([self.g_lat[l] * nbD for l in range(self.Ld)])
        for l in range(self.Ld):
            dD[l] += self.g_vert[l] + (self.g_vert[l - 1] if l else 0.0)
        dS = self.g_lat[-1] * nbS + self.g_pkg
        m, n = self.m, self.n
        dS[m:m + n, m:m + n] += self.g_vert[self.Ld - 1]
        return dD, dS

    @property
    def size(self) -> int:
        return self.Ld * self.n * self.n + self.N * self.N

    def matrix(self):
        """G as a scipy CSR matrix over x = concat(D.ravel(), S.ravel())."""
        import scipy.sparse as sp
        n, N, m, Ld = self.n, self.N, self.m, self.Ld
        iD = np.arange(Ld * n * n).reshape(Ld, n, n)
        iS = Ld * n * n + np.arange(N * N).reshape(N, N)
        rows, cols, vals = [], [], []

        def couple(a, b, g):
            a, b = a.ravel(), b.ravel()
            gv = np.full(a.size, g)
            rows.extend([a, b, a, b])
            cols.extend([a, b, b, a])
            vals.extend([gv, gv, -gv, -gv])

        for l in range(Ld):
            couple(iD[l, :, :-1], iD[l, :, 1:], self.g_lat[l])
            couple(iD[l, :-1, :], iD[l, 1:, :], self.g_lat[l])
        for l in range(Ld - 1):
            couple(iD[l], iD[l + 1], self.g_vert[l])
        couple(iD[Ld - 1], iS[m:m + n, m:m + n], self.g_vert[Ld - 1])
        couple(iS[:, :-1], iS[:, 1:], self.g_lat[-1])
        couple(iS[:-1, :], iS[1:, :], self.g_lat[-1])
        rows.append(iS.ravel())
        cols.append(iS.ravel())
        vals.append(np.full(N * N, self.g_pkg))
        size = self.size
        return sp.csr_matrix((np.concatenate(vals), (np.concatenate(rows),
                                                     np.concatenate(cols))),
                             shape=(size, size))

    def capacities(self) -> np.ndarray:
        """Heat capacity per unknown [J/K], in the order of `matrix`."""
        n, N = self.n, self.N
        return np.concatenate([np.repeat(self.cap[:self.Ld], n * n),
                               np.full(N * N, self.cap[-1])])


@partial(jax.jit, static_argnums=(0,))
def _pcg(op: Operator, rD, rS, dD, dS):
    """Jacobi-PCG for G d = r in the dtype of ``r``; returns (dD, dS)."""
    dt = rD.dtype

    def dot(a, b):
        return jnp.sum(a[0] * b[0]) + jnp.sum(a[1] * b[1])

    scale = jnp.sqrt(dot((rD, rS), (rD, rS)))
    scale = jnp.where(scale > 0, scale, jnp.ones((), dt))
    r = (rD / scale, rS / scale)
    x = (jnp.zeros_like(rD), jnp.zeros_like(rS))
    z = (r[0] / dD, r[1] / dS)
    rz = dot(r, z)

    def cond(state):
        _, r, _, _, it = state
        return (jnp.sqrt(dot(r, r)) > CG_TOL) & (it < CG_MAX_ITER)

    def body(state):
        x, r, p, rz, it = state
        Ap = op.apply(p[0], p[1], jnp)
        pAp = dot(p, Ap)
        ok = pAp > 0
        alpha = jnp.where(ok, rz / jnp.where(ok, pAp, 1), 0).astype(dt)
        x = (x[0] + alpha * p[0], x[1] + alpha * p[1])
        r = (r[0] - alpha * Ap[0], r[1] - alpha * Ap[1])
        z = (r[0] / dD, r[1] / dS)
        rz_new = dot(r, z)
        beta = jnp.where(rz > 0, rz_new / jnp.where(rz > 0, rz, 1), 0)
        beta = beta.astype(dt)
        p = (z[0] + beta * p[0], z[1] + beta * p[1])
        return x, r, p, rz_new, it + 1

    x, *_ = jax.lax.while_loop(cond, body, (x, r, z, rz, jnp.int32(0)))
    return x[0] * scale, x[1] * scale


def steady_rise(power: np.ndarray, config: dict, n: int, margin: int,
                die_w_m: float, dtype: str = "float64"
                ) -> tuple[np.ndarray, dict]:
    """Temperature rise over ambient of the die layers [Ld, n, n] in K.

    ``dtype="float64"`` is the reference; a lower dtype (``"bfloat16"``)
    is the control: residual and correction both in that dtype.
    Returns (rise as float64, {"rounds", "rel_residual"}).
    """
    op = Operator(config, n, margin, die_w_m)
    bD = np.asarray(power, np.float64)
    if bD.shape != (op.Ld, n, n):
        raise ValueError(f"power {bD.shape} != {(op.Ld, n, n)}")
    bS = np.zeros((op.N, op.N))
    bnorm = math.sqrt(float((bD ** 2).sum()))
    dD, dS = op.diagonal()
    host = dtype == "float64"
    work = jnp.float32 if host else jnp.dtype(dtype)
    ddD, ddS = jnp.asarray(dD, work), jnp.asarray(dS, work)
    if host:
        xD, xS = np.zeros_like(bD), np.zeros_like(bS)
    else:
        xD, xS = jnp.zeros(bD.shape, work), jnp.zeros(bS.shape, work)
        bD_w, bS_w = jnp.asarray(bD, work), jnp.asarray(bS, work)
    rel, rounds = math.inf, 0
    for rounds in range(1, (MAX_ROUNDS if host else CONTROL_ROUNDS) + 1):
        if host:
            yD, yS = op.apply(xD, xS, np)
            rD, rS = bD - yD, bS - yS
            rel = math.sqrt(float((rD ** 2).sum() + (rS ** 2).sum())) / bnorm
        else:
            yD, yS = op.apply(xD, xS, jnp)
            rD, rS = bD_w - yD, bS_w - yS
            rel = math.sqrt(float(jnp.sum(rD.astype(jnp.float32) ** 2)
                                  + jnp.sum(rS.astype(jnp.float32) ** 2))) \
                / bnorm
        if rel < RTOL:
            break
        cD, cS = _pcg(op, jnp.asarray(rD, work), jnp.asarray(rS, work),
                      ddD, ddS)
        if host:
            xD = xD + np.asarray(cD, np.float64)
            xS = xS + np.asarray(cS, np.float64)
        else:
            xD, xS = xD + cD, xS + cS
    return np.asarray(xD, np.float64), {"rounds": rounds, "rel_residual": rel}
