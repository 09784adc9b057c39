"""The walk-start search: each lane's start is the number of residual
cumsum entries strictly below its draw (``random_walk._count_below``).

- On a non-decreasing cumsum (the host's sequential float32 ``np.cumsum``)
  it equals ``jnp.searchsorted(csum, u, side="left")`` bit for bit, on
  plateaus of zero residual (leading, inner and trailing), one non-zero
  node, draws equal to cumsum entries, zero draws, draws past the last
  entry, a lane count that is not a multiple of 128, and n = 1.
- On ``jnp.cumsum`` output, which XLA's parallel prefix sum can leave
  with a dip of a few ulps, it equals the count by comparison with every
  entry.
- Under ``vmap`` it gives each row's own count, with the cumsum batched
  or shared; ``per_query``, which the fused path calls the walks through,
  gives ``vmap``'s answer for a batch of one (run on its row alone) and
  of three.
- ``sample_walk_starts`` gives the counts clipped to n - 1, and a quarter
  of the draws searched alone gives that quarter of the lanes' starts: the
  node-sharded path's lane slice commutes with the search.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ppr.random_walk import (_count_below, per_query,
                                   sample_walk_starts)

LANES = 1000            # not a multiple of 128
SHARDS = 4


def _draws(seed: int, top: float, lanes: int = LANES) -> np.ndarray:
    return (np.random.default_rng(seed).random(lanes) * top).astype(
        np.float32)


def _mixed(seed: int, n: int) -> np.ndarray:
    """Residuals of which about half are zero."""
    rng = np.random.default_rng(seed)
    return (rng.random(n) * (rng.random(n) < 0.5)).astype(np.float32)


def _case(name: str) -> tuple[np.ndarray, np.ndarray]:
    """(csum, draws) with csum non-decreasing."""
    if name == "plateaus":
        r = _mixed(1, 300)
        r[:20] = 0.0
        r[-30:] = 0.0
        csum = np.cumsum(r, dtype=np.float32)
        return csum, _draws(2, float(csum[-1]))
    if name == "one_nonzero":
        r = np.zeros(300, np.float32)
        r[137] = 0.25
        csum = np.cumsum(r, dtype=np.float32)
        return csum, _draws(3, float(csum[-1]))
    if name == "ties":
        csum = np.cumsum(_mixed(4, 300), dtype=np.float32)
        u = csum[np.random.default_rng(5).integers(0, 300, LANES)]
        return csum, u.astype(np.float32)
    if name == "zero_draws":
        csum = np.cumsum(_mixed(6, 300), dtype=np.float32)
        u = _draws(7, float(csum[-1]))
        u[::3] = 0.0
        return csum, u
    if name == "past_the_end":
        csum = np.cumsum(_mixed(8, 300), dtype=np.float32)
        return csum, _draws(9, float(csum[-1])) + np.float32(2 * csum[-1])
    if name == "one_node":
        csum = np.array([0.5], np.float32)
        return csum, _draws(10, 1.0)
    raise ValueError(name)


CASES = ("plateaus", "one_nonzero", "ties", "zero_draws", "past_the_end",
         "one_node")


@pytest.mark.parametrize("name", CASES)
def test_count_equals_searchsorted_on_a_monotone_cumsum(name):
    csum, u = _case(name)
    assert (np.diff(csum) >= 0).all()
    got = jax.jit(_count_below)(jnp.asarray(csum), jnp.asarray(u))
    want = jnp.searchsorted(jnp.asarray(csum), jnp.asarray(u), side="left")
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if name == "past_the_end":
        assert (np.asarray(got) == csum.shape[0]).all()


@pytest.mark.parametrize("seed", [1, 2])
def test_count_on_a_device_cumsum_with_a_dip(seed):
    """Residuals of n = 300, about half zero: XLA's cumsum of seed 1 dips
    (csum[288] < csum[287]); draws are placed on both sides of every
    entry, so lanes land inside the dip."""
    csum = jnp.cumsum(jnp.asarray(_mixed(seed, 300)))
    c = np.asarray(csum)
    if seed == 1:
        assert (np.diff(c) < 0).any(), "the cumsum no longer dips"
    u = np.concatenate([c, np.nextafter(c, np.float32(np.inf)),
                        np.nextafter(c, np.float32(0)),
                        _draws(seed, float(c.max()))]).astype(np.float32)
    got = jax.jit(_count_below)(csum, jnp.asarray(u))
    want = jnp.sum(csum[None, :] < jnp.asarray(u)[:, None], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shared", [False, True])
def test_count_under_vmap_is_each_rows_count(shared):
    csum = jnp.cumsum(jnp.stack([jnp.asarray(_mixed(s, 300))
                                 for s in (13, 14, 15)]), axis=1)
    u = jnp.stack([jnp.asarray(_draws(s, float(csum[i, -1])))
                   for i, s in enumerate((16, 17, 18))])
    if shared:
        got = jax.vmap(_count_below, in_axes=(None, 0))(csum[0], u)
        rows = [_count_below(csum[0], row) for row in u]
    else:
        got = jax.jit(jax.vmap(_count_below))(csum, u)
        rows = [_count_below(c, row) for c, row in zip(csum, u)]
    np.testing.assert_array_equal(np.asarray(got), np.stack(rows))


@pytest.mark.parametrize("rows", [1, 3])
def test_per_query_equals_vmap(rows):
    residual = jnp.stack([jnp.asarray(_mixed(s, 300))
                          for s in range(20, 20 + rows)])
    keys = jax.random.split(jax.random.PRNGKey(19), rows)

    def starts(r, k):
        return sample_walk_starts(r, k, num_walks=LANES, n=300)

    got = jax.jit(lambda r, k: per_query(starts, r, k))(residual, keys)
    want = jax.jit(jax.vmap(starts))(residual, keys)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_starts_clip_and_commute_with_the_lane_slice():
    n = 300
    residual = jnp.asarray(_mixed(11, n))
    key = jax.random.PRNGKey(12)
    starts, r_sum = sample_walk_starts(residual, key, num_walks=LANES * 4,
                                       n=n)
    # the draws sample_walk_starts makes: same key split, same op order
    k_start, _ = jax.random.split(key)
    u = jax.random.uniform(k_start, (LANES * 4,)) * r_sum
    csum = jnp.cumsum(residual)
    quarter = LANES * 4 // SHARDS
    parts = [_count_below(csum, u[i * quarter:(i + 1) * quarter])
             for i in range(SHARDS)]
    np.testing.assert_array_equal(
        np.asarray(starts), np.minimum(np.concatenate(parts), n - 1))
    one, _ = sample_walk_starts(jnp.ones(1, jnp.float32), key,
                                num_walks=7, n=1)
    np.testing.assert_array_equal(np.asarray(one), np.zeros(7, np.int32))
