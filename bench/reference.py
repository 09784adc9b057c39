"""Plain references for the check, independent of the program.

Everything here is built from the generator's edge list alone (never from
the program's ``Graph``, tables or weights):

- :func:`exact_ppr`: PPR rows by power iteration, float64 on the host
  (scipy's sparse product), in blocks of sources. Its fixed point is
  ``pi = alpha e_s + (1 - alpha) P^T pi`` with ``P = D_out^-1 A``: a walk
  stops with probability alpha at each node, else moves to a uniform
  out-neighbour. A copy of the repository's ``ppr/power_iteration.py``
  semantics, computed in float64.
- :func:`push_reference`: FORA's forward push by synchronous sweeps, float64:
  each sweep relaxes every node whose residual exceeds ``rmax * deg_out``.
  Its residual mass ``r_sum`` is what the walk phase is sized from.
- :func:`control_answers`: the control, both of the above computed in
  bfloat16 with JAX on the device, put in the program's place. A check that
  passes it is too loose to notice a program that drops to bfloat16.
"""

from __future__ import annotations

import math

import numpy as np
import scipy.sparse as sp

BLOCK = 16   # sources per block of the host reference


def arcs(n: int, src: np.ndarray, dst: np.ndarray,
         directed: bool) -> tuple[np.ndarray, np.ndarray]:
    """The arcs of the generator's edges: both directions if undirected."""
    if directed:
        return src, dst
    return np.concatenate([src, dst]), np.concatenate([dst, src])


def transition_t(n: int, src: np.ndarray, dst: np.ndarray) -> sp.csr_matrix:
    """``P^T`` as a float64 CSR matrix: entry (t, v) is 1/deg_out(v) for an
    arc v -> t. Every node has an out-arc (the generator guarantees it)."""
    deg = np.bincount(src, minlength=n)
    if np.any(deg == 0):
        raise ValueError("the reference needs every node to have an out-arc")
    w = 1.0 / deg[src].astype(np.float64)
    return sp.csr_matrix((w, (dst, src)), shape=(n, n))


def fora_params(n: int, m: int, epsilon: float) -> tuple[float, float]:
    """FORA's push threshold ``rmax`` and walk budget ``omega`` at
    delta = p_f = 1/n (Wang et al., KDD'17): a node is pushed while its
    residual exceeds ``rmax * deg_out``, and ceil(r_sum * omega) walks
    meet the guarantee."""
    log_term = math.log(2.0 * n)
    rmax = epsilon * math.sqrt(1.0 / n / (3.0 * m * log_term))
    omega = (2.0 * epsilon / 3.0 + 2.0) * log_term / (epsilon ** 2 / n)
    return rmax, omega


def iterations(alpha: float, tol: float) -> int:
    """Power-iteration steps after which the L1 error is below ``tol``."""
    return math.ceil(math.log(tol) / math.log(1.0 - alpha))


def exact_ppr(pt: sp.csr_matrix, sources: np.ndarray, *, alpha: float,
              tol: float = 1e-12) -> np.ndarray:
    """(len(sources), n) float64 PPR rows, to an L1 error under ``tol``."""
    n = pt.shape[0]
    steps = iterations(alpha, tol)
    rows = []
    for lo in range(0, len(sources), BLOCK):
        block = np.asarray(sources[lo:lo + BLOCK])
        seed = np.zeros((n, block.size))
        seed[block, np.arange(block.size)] = alpha
        x = seed.copy()
        for _ in range(steps):
            x = seed + (1.0 - alpha) * (pt @ x)
        rows.append(x.T)
    return np.concatenate(rows)


def push_reference(pt: sp.csr_matrix, out_degree: np.ndarray,
                   sources: np.ndarray, *, alpha: float, rmax: float,
                   max_sweeps: int = 10_000) -> tuple[np.ndarray, np.ndarray]:
    """FORA's synchronous forward push, float64. Returns each source's
    residual mass after the push and its number of sweeps."""
    n = pt.shape[0]
    threshold = rmax * np.maximum(out_degree, 1).astype(np.float64)
    r_sum, sweeps = [], []
    for lo in range(0, len(sources), BLOCK):
        block = np.asarray(sources[lo:lo + BLOCK])
        r = np.zeros((n, block.size))
        r[block, np.arange(block.size)] = 1.0
        count = np.zeros(block.size, np.int64)
        for _ in range(max_sweeps):
            front = r > threshold[:, None]
            active = front.any(axis=0)
            if not active.any():
                break
            count += active
            pushed = np.where(front, r, 0.0)
            r = r - pushed + (1.0 - alpha) * (pt @ pushed)
        r_sum.append(r.sum(axis=0))
        sweeps.append(count)
    return np.concatenate(r_sum), np.concatenate(sweeps)


def control_answers(n: int, src: np.ndarray, dst: np.ndarray,
                    sources: np.ndarray, *, alpha: float, rmax: float,
                    steps: int, dtype: str = "bfloat16",
                    max_sweeps: int = 10_000
                    ) -> tuple[np.ndarray, np.ndarray]:
    """The control: the push and the power iteration above, computed in
    ``dtype`` with JAX, one source at a time. Returns ``(pi rows, r_sum)``
    as float64 host arrays, in the shape the check reads from the program."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    deg = np.bincount(src, minlength=n)
    w = jnp.asarray((1.0 / deg[src]).astype(np.float32), dt)
    s, d = jnp.asarray(src), jnp.asarray(dst)
    a = jnp.asarray(alpha, dt)
    thr = jnp.asarray(rmax * np.maximum(deg, 1), dt)

    def spread(x):
        return jax.ops.segment_sum(x[s] * w, d, num_segments=n)

    @jax.jit
    def one(source):
        e = jnp.zeros(n, dt).at[source].set(1)

        def cond(c):
            r, k = c
            return jnp.logical_and(jnp.any(r > thr), k < max_sweeps)

        def sweep(c):
            r, k = c
            pushed = jnp.where(r > thr, r, jnp.zeros((), dt))
            return r - pushed + (1 - a) * spread(pushed), k + 1

        r, _ = jax.lax.while_loop(cond, sweep, (e, jnp.int32(0)))
        pi = jax.lax.fori_loop(0, steps,
                               lambda _, x: a * e + (1 - a) * spread(x),
                               a * e)
        return pi.astype(jnp.float32), r.astype(jnp.float32).sum()

    pis, sums = [], []
    for source in np.asarray(sources):
        pi, r_sum = one(jnp.int32(source))
        pis.append(np.asarray(pi, np.float64))
        sums.append(float(r_sum))
    return np.stack(pis), np.asarray(sums)
