"""Plain reference of the field-estimation service, independent of the
program: it imports nothing of ``repro`` and reads nothing the program
made.  It is built from the deployment's raw data alone: sensor
positions, radius, kernel width, regularizer and readings.

Semantics (cs/0507039 Table 1 and Sec. 3.3):

* Sensor ``s`` keeps a local function over its anchors: its neighbors
  within ``radius`` (itself included) and the measurements absorbed at
  it.  A projection step solves
  ``c_s = (K_s + lambda I)^{-1} (z_{N_s} + lambda c_s)`` and writes
  ``z_j = f_s(x_j)`` back to every anchor's message.
* A sweep visits the sensors class by class of a greedy distance-2
  colouring (Welsh-Powell order: decreasing degree in the squared graph,
  smallest free colour).  Sensors of one class share no anchor, so a
  class is one simultaneous step, equal to visiting its sensors one by
  one.  This is the program's sweep order, so its iterates after any
  number of sweeps are the reference's up to rounding.
* An absorbed measurement ``(field, sensor, x, y)`` gives the sensor a
  new anchor at ``x`` with its own message, initialised to ``y``, and a
  zero coefficient.
* A query's answer is the mean over the ``k`` nearest sensors of their
  local functions at the query (kNN fusion, Eq. 19), by brute force over
  all sensors.

Arithmetic: the local Grams and their Cholesky factors are computed on
the host in float64 and stored as float32; the sweeps and answers run on
the device in float32 with elementwise products and sums only (triangular
substitution, Gram times coefficients, kernel values), so no matrix unit
or precision setting of the platform enters.  ``precision="highest"`` is
the configuration's stated float32; ``"bf16x3"`` computes the Gram times
coefficients product as three bfloat16 passes (what a TPU matrix unit
does at ``precision="high"``): the control, one step below it.
``"bf16"`` is one bfloat16 pass (the TPU's default precision), a step
further down.

Memory: fields are independent, so the per-field state (messages,
coefficients, absorbed anchors, and the (n+1, L, L) Gram and Cholesky
factors of every field, which dominate) is held in blocks of fields.
Where the whole state fits in ``BUDGET_SHARE`` of the device's memory it
is one block, always on the device.  Otherwise each block lives on the
host and is moved to the device for each call, one block at a time, and
the calls' results are concatenated over the blocks.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp


BUDGET_SHARE = 0.25
"""Share of the device's memory (its ``bytes_limit``) that one reference's
per-field state may take.  A quarter leaves room for what a call makes
beside it: an absorb's updated Gram and Cholesky factors are new arrays
while the old ones live (2x those), a sweep's per-class gathers of them,
an answer's anchor gathers, and the shadows ``calibrate.py`` feeds the
same calls, each a reference of its own."""


def state_budget() -> float:
    """Bytes of per-field state that one block may hold: ``BUDGET_SHARE``
    of the device's memory, or no limit where the platform reports none
    (the CPU)."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return BUDGET_SHARE * limit if limit else float("inf")


def adjacency(pos: np.ndarray, radius: float) -> np.ndarray:
    """Bool (n, n): ||x_i - x_j|| < radius, self included."""
    p = np.asarray(pos, np.float64)
    d2 = ((p[:, None, :] - p[None, :, :]) ** 2).sum(-1)
    adj = d2 < radius * radius
    np.fill_diagonal(adj, True)
    return adj


def colour_classes(adj: np.ndarray) -> list:
    """Greedy distance-2 colour classes, in colour order."""
    a = adj.astype(np.float32)
    conflict = (a @ a) > 0
    np.fill_diagonal(conflict, False)
    order = np.argsort(-conflict.sum(1), kind="stable")
    colours = -np.ones(len(adj), np.int64)
    for v in order:
        used = set(colours[conflict[v]].tolist())
        c = 0
        while c in used:
            c += 1
        colours[v] = c
    return [np.nonzero(colours == c)[0] for c in range(int(colours.max()) + 1)]


def factors(anchors, mask, lam, gamma):
    """Masked local Grams and Cholesky factors of K + lambda I, computed on
    the host in float64 and returned as float32.

    anchors (..., L, d), mask (..., L), lam (...,).  Off-mask rows and
    columns of K are zero and their diagonal is 1, so their solves give
    exactly zero.
    """
    a = np.asarray(anchors, np.float64)
    mask = np.asarray(mask, bool)
    k = np.exp(-gamma * ((a[..., :, None, :] - a[..., None, :, :]) ** 2).sum(-1))
    k = np.where(mask[..., :, None] & mask[..., None, :], k, 0.0)
    diag = np.where(mask, np.asarray(lam, np.float64)[..., None], 1.0)
    chol = np.linalg.cholesky(k + diag[..., None, :] * np.eye(k.shape[-1]))
    return k.astype(np.float32), chol.astype(np.float32)


def _matvec(a, x, precision):
    """(..., L, L) @ (..., L) by elementwise products and a sum."""
    if precision == "highest":
        return jnp.sum(a * x[..., None, :], axis=-1)
    if precision not in ("bf16x3", "bf16"):
        raise ValueError(f"unknown precision {precision!r}")

    def split(v):
        hi = _to_bf16(v)
        return hi, _to_bf16(v - hi)

    ah, al = split(a)
    xh, xl = split(x)
    prod = ah * xh[..., None, :]
    if precision == "bf16x3":
        prod = prod + (ah * xl[..., None, :] + al * xh[..., None, :])
    return jnp.sum(prod, axis=-1)


def _to_bf16(v):
    """float32 rounded to bfloat16 (nearest, ties to even), kept as float32.

    Done on the bits: a compiler may drop a float32 -> bfloat16 -> float32
    round trip of converts as excess precision, and the XLA TPU compiler
    does.
    """
    u = jax.lax.bitcast_convert_type(v, jnp.uint32)
    u = u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(u & jnp.uint32(0xFFFF0000), jnp.float32)


def _cho_solve(chol, rhs):
    """(L L^T)^{-1} rhs by forward then back substitution, row by row."""
    n = chol.shape[-1]
    y = jnp.zeros_like(rhs)
    for i in range(n):
        yi = (rhs[..., i] - jnp.sum(chol[..., i, :] * y, axis=-1)) / chol[..., i, i]
        y = y.at[..., i].set(yi)
    x = jnp.zeros_like(rhs)
    for i in reversed(range(n)):
        xi = (y[..., i] - jnp.sum(chol[..., :, i] * x, axis=-1)) / chol[..., i, i]
        x = x.at[..., i].set(xi)
    return x


def _rbf(x, a, gamma):
    """exp(-gamma ||x - a||^2) over the last axis (direct difference form)."""
    return jnp.exp(-gamma * jnp.sum((x - a) ** 2, axis=-1))


@partial(jax.jit, static_argnames=("sweeps", "precision"), donate_argnums=(0, 1, 2))
def _sweeps(z, zp, coef, nbr, nmask, pmask, lam, gram, chol, classes, sweeps, precision):
    """``sweeps`` sweeps; z (B, n+1), zp (B, n+1, P), coef (B, n+1, L).

    Row n is a dummy sensor: padded class members and masked lanes point
    at it, and every lane it has is masked.
    """
    b = z.shape[0]
    dn = nbr.shape[1]
    dummy = z.shape[1] - 1

    def colour_step(carry, members):
        z, zp, coef = carry
        idx = nbr[members]  # (M, D)
        nm = nmask[members]  # (M, D)
        pm = pmask[:, members]  # (B, M, P)
        zin = jnp.concatenate([z[:, idx], zp[:, members]], axis=-1)
        mask = jnp.concatenate([jnp.broadcast_to(nm, (b,) + nm.shape), pm], axis=-1)
        rhs = jnp.where(mask, zin + lam[members][None, :, None] * coef[:, members], 0.0)
        c_new = _cho_solve(chol[:, members], rhs)
        vals = _matvec(gram[:, members], c_new, precision)
        tgt = jnp.where(nm, idx, dummy).reshape(-1)
        z = z.at[:, tgt].set(vals[..., :dn].reshape(b, -1))
        zp = zp.at[:, members].set(jnp.where(pm, vals[..., dn:], zp[:, members]))
        coef = coef.at[:, members].set(c_new)
        z = z.at[:, dummy].set(0.0)
        return (z, zp, coef), None

    def sweep(carry, _):
        carry, _ = jax.lax.scan(colour_step, carry, classes)
        return carry, None

    (z, zp, coef), _ = jax.lax.scan(sweep, (z, zp, coef), None, length=sweeps)
    return z, zp, coef


@partial(jax.jit, static_argnames=("gamma", "k"))
def _answer_block(xq, pos, nbr, nmask, pp, pmask, coef, gamma, k):
    """(B, Q) kNN-fusion answers and (Q,) near-tie flags for one block."""
    d2 = jnp.sum((xq[:, None, :] - pos[None, :, :]) ** 2, axis=-1)  # (Q, n)
    neg, sel = jax.lax.top_k(-d2, k + 1)
    kth, nxt = -neg[:, k - 1], -neg[:, k]
    # selections that rounding of the distances could swap
    tie = (nxt - kth) <= 1e-5 * jnp.maximum(kth, 1e-12)
    sel = sel[:, :k]  # (Q, k)
    base = pos[nbr[sel]]  # (Q, k, D, d)
    b = coef.shape[0]
    anchors = jnp.concatenate(
        [jnp.broadcast_to(base, (b,) + base.shape), pp[:, sel]], axis=-2
    )  # (B, Q, k, L, d)
    mask = jnp.concatenate(
        [jnp.broadcast_to(nmask[sel], (b,) + nmask[sel].shape), pmask[:, sel]], axis=-1
    )
    kv = _rbf(xq[None, :, None, None, :], anchors, gamma)
    f = jnp.sum(jnp.where(mask, kv * coef[:, sel], 0.0), axis=-1)  # (B, Q, k)
    return jnp.mean(f, axis=-1), tie


class _Block:
    """The per-field state of fields ``lo`` .. ``hi`` - 1, on the device or
    parked on the host."""

    STATE = ("z", "zp", "coef", "pp", "pmask", "gram", "chol")
    FACTORS = ("pp", "pmask", "gram", "chol")  # changed by absorbs alone

    def __init__(self, lo: int, hi: int, **state):
        self.lo, self.hi = lo, hi
        self.__dict__.update(state)
        self.host = None  # the parked factors, while they are unchanged on the device

    def load(self) -> None:
        """To the device; the factors' host copies are kept."""
        self.host = {k: getattr(self, k) for k in self.FACTORS}
        for k in self.STATE:
            setattr(self, k, jnp.asarray(getattr(self, k)))

    def park(self, absorbed: bool) -> None:
        """To the host, downloading the factors only where an absorb changed
        them."""
        for k in self.STATE:
            keep = k in self.FACTORS and not absorbed
            setattr(self, k, self.host[k] if keep else np.array(getattr(self, k)))
        self.host = None


class Reference:
    """Reference state for B fields over one network.

    pos (n, d), ys (B, n) readings, ``lanes`` private anchor lanes per
    (field, sensor) for absorbed measurements.  ``budget``: bytes of
    per-field state one block may hold (default ``state_budget()``).
    """

    def __init__(self, pos, radius, gamma, lam, ys, lanes=0, precision="highest",
                 budget=None):
        pos = np.asarray(pos, np.float32)
        n, d = pos.shape
        adj = adjacency(pos, radius)
        dn = int(adj.sum(1).max())
        nbr = np.full((n + 1, dn), n, np.int32)
        nmask = np.zeros((n + 1, dn), bool)
        for i in range(n):
            nb = np.nonzero(adj[i])[0]
            nbr[i, : len(nb)] = nb
            nmask[i, : len(nb)] = True
        self.classes_list = colour_classes(adj)
        m = max(len(c) for c in self.classes_list)
        classes = np.full((len(self.classes_list), m), n, np.int32)
        for i, c in enumerate(self.classes_list):
            classes[i, : len(c)] = c
        b = ys.shape[0]
        p = max(int(lanes), 1)  # one always-masked lane keeps the shapes uniform
        self.n, self.d, self.b, self.dn, self.p = n, d, b, dn, int(lanes)
        self.gamma, self.lam_f = float(gamma), float(lam)
        self.precision = precision
        self.pos1 = np.concatenate([pos, np.zeros((1, d), np.float32)])
        self.nbr_np, self.nmask_np = nbr, nmask
        self.pp_np = np.zeros((b, n + 1, p, d), np.float32)
        self.pmask_np = np.zeros((b, n + 1, p), bool)
        self.used = np.zeros((b, n + 1), np.int64)
        self.pos = jnp.asarray(self.pos1)
        self.nbr = jnp.asarray(nbr)
        self.nmask = jnp.asarray(nmask)
        self.lam = jnp.full((n + 1,), lam, jnp.float32)
        self.classes = jnp.asarray(classes)
        # before any absorb every field has the same factors
        anchors = np.concatenate([self.pos1[nbr], np.zeros((n + 1, p, d), np.float32)], 1)
        mask = np.concatenate([nmask, np.zeros((n + 1, p), bool)], axis=1)
        gram, chol = factors(anchors, mask, np.full(n + 1, lam), self.gamma)
        z = np.concatenate([ys, np.zeros((b, 1), np.float32)], 1)
        lanes_all = dn + p
        # bytes of one field's z, zp, coef, pp, gram, chol (float32), pmask
        self.field_bytes = (n + 1) * (4 * (1 + p + lanes_all * (1 + 2 * lanes_all) + p * d) + p)
        budget = state_budget() if budget is None else budget
        size = int(max(1, min(b, budget // self.field_bytes)))
        self.blocks = []
        for lo in range(0, b, size):
            hi = min(lo + size, b)
            if size == b:  # one block: on the device throughout
                state = dict(
                    z=jnp.asarray(z), zp=jnp.zeros((b, n + 1, p), jnp.float32),
                    coef=jnp.zeros((b, n + 1, lanes_all), jnp.float32),
                    pp=jnp.asarray(self.pp_np), pmask=jnp.asarray(self.pmask_np),
                    gram=jnp.broadcast_to(jnp.asarray(gram), (b,) + gram.shape),
                    chol=jnp.broadcast_to(jnp.asarray(chol), (b,) + chol.shape))
            else:
                f = hi - lo
                state = dict(
                    z=z[lo:hi], zp=np.zeros((f, n + 1, p), np.float32),
                    coef=np.zeros((f, n + 1, lanes_all), np.float32),
                    pp=self.pp_np[lo:hi].copy(), pmask=self.pmask_np[lo:hi].copy(),
                    gram=np.broadcast_to(gram, (f,) + gram.shape),
                    chol=np.broadcast_to(chol, (f,) + chol.shape))
            self.blocks.append(_Block(lo, hi, **state))

    def _each(self, blocks=None, absorbed: bool = False):
        """Each block (of ``blocks``, default all) on the device in turn;
        parked again after it, where there are several."""
        for blk in self.blocks if blocks is None else blocks:
            if len(self.blocks) == 1:
                yield blk
                continue
            blk.load()
            yield blk
            blk.park(absorbed)

    def sweeps(self, count: int) -> None:
        if count <= 0:
            return
        for blk in self._each():
            blk.z, blk.zp, blk.coef = _sweeps(
                blk.z, blk.zp, blk.coef, self.nbr, self.nmask, blk.pmask,
                self.lam, blk.gram, blk.chol, self.classes, count, self.precision,
            )

    def absorb(self, fields, sensors, xs, ys) -> int:
        """Give each (field, sensor) a new anchor; returns how many fit."""
        rows = []
        for i, (f, s) in enumerate(zip(fields, sensors)):
            j = self.used[f, s]
            if j < self.p:
                self.used[f, s] = j + 1
                rows.append((f, s, j, i))
        if not rows:
            return 0
        f, s, lane, keep = (np.asarray(v) for v in zip(*rows))
        self.pp_np[f, s, lane] = xs[keep]
        self.pmask_np[f, s, lane] = True
        anchors = np.concatenate([self.pos1[self.nbr_np[s]], self.pp_np[f, s]], axis=1)
        mask = np.concatenate([self.nmask_np[s], self.pmask_np[f, s]], axis=1)
        gram, chol = factors(anchors, mask, np.full(len(s), self.lam_f), self.gamma)
        val = ys[keep]
        touched = [blk for blk in self.blocks if np.any((f >= blk.lo) & (f < blk.hi))]
        for blk in self._each(touched, absorbed=True):
            mine = (f >= blk.lo) & (f < blk.hi)
            fb, sb, lb = (jnp.asarray(v[mine]) for v in (f - blk.lo, s, lane))
            blk.pp = jnp.asarray(self.pp_np[blk.lo:blk.hi])
            blk.pmask = jnp.asarray(self.pmask_np[blk.lo:blk.hi])
            blk.zp = blk.zp.at[fb, sb, lb].set(jnp.asarray(val[mine]))
            blk.coef = blk.coef.at[fb, sb, self.dn + lb].set(0.0)
            blk.gram = blk.gram.at[fb, sb].set(jnp.asarray(gram[mine]))
            blk.chol = blk.chol.at[fb, sb].set(jnp.asarray(chol[mine]))
        return len(rows)

    def answer(self, xq: np.ndarray, k: int):
        """(B, Q) answers and (Q,) near-tie flags at query points xq."""
        xq = np.asarray(xq, np.float32)
        outs = []
        for blk in self._each():
            lanes = self.dn + blk.pp.shape[2]
            b = blk.hi - blk.lo
            block = int(max(8, min(1024, 2**22 // max(1, b * k * lanes * self.d))))
            out, ties = [], []
            for i in range(0, len(xq), block):
                xb = xq[i:i + block]
                rows = len(xb)
                if rows < block:  # one compiled shape per block size
                    xb = np.concatenate([xb, np.repeat(xb[-1:], block - rows, 0)])
                o, t = _answer_block(
                    jnp.asarray(xb), self.pos[:-1], self.nbr, self.nmask,
                    blk.pp, blk.pmask, blk.coef, self.gamma, k,
                )
                out.append(np.asarray(o)[:, :rows])
                ties.append(np.asarray(t)[:rows])
            outs.append(np.concatenate(out, 1))
        return np.concatenate(outs, 0), np.concatenate(ties)  # ties: any block's

    def messages(self) -> np.ndarray:
        """(B, n) messages of the sensors' own slots."""
        return np.concatenate([np.asarray(blk.z[:, : self.n]) for blk in self._each()])

    def slots(self) -> np.ndarray:
        """(B, n + n P) every live message slot: the sensors' own and the
        absorbed measurements'."""
        zp = np.concatenate([np.asarray(blk.zp[:, : self.n]) for blk in self._each()])
        return np.concatenate([self.messages(), zp.reshape(self.b, -1)], axis=1)

    def coefficients(self) -> np.ndarray:
        """(B, n, D) coefficients of the neighbor lanes, neighbors ascending."""
        return np.concatenate([np.asarray(blk.coef[:, : self.n, : self.dn])
                               for blk in self._each()])
