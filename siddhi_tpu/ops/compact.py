"""Fixed-size compaction of a boolean mask, without a scatter.

``jnp.nonzero(flat, size=cap, fill_value=-1)`` *places* every element
of the mask: in jax 0.9 it is ``cumsum(bincount(cumsum(flat),
length=cap))``, and the ``bincount`` a scatter-add of all of them, which
a TPU does one element after another (8.6 ns each: 36 ms for the 4M
elements of a ``[16384, 32, 8]`` match mask, to find a thousand rows).
Here the j-th set element is *found* instead, by a search over prefix
counts (0.2 ms for the same mask; PERF.md, PR 35)."""
import jax.numpy as jnp

#: row width where the mask's own rows are too few and too long to use
#: (a join's ``[1, m]`` probe, an unpartitioned window): one lane tile
_TILE = 128


def compact_indices(mask, cap):
    """→ ([cap] int32, count): the row-major flat indices of `mask`'s
    set elements, ascending, ``-1`` beyond their count — ``jnp.nonzero(
    mask.reshape(-1), size=cap, fill_value=-1)[0]``, letter for letter —
    and the true count of set elements, which may exceed `cap`.

    Two levels over the mask as ``[L, W]`` rows: per-row counts and
    their prefix sums; for each j = 1…cap the row by a search over those
    L sums and the rank inside it; then the bits of the `cap` rows found,
    prefix-summed along the row, give the column.  One pass over the
    mask, a log2(L)-step search, and dense work over ``[cap, W]``.  The
    rows are the mask's own (its leading axis: no relayout, and under a
    mesh the counts stay sharded with it) where there are at least `cap`
    of them, so that ``[cap, W]`` is at most the mask's size; else the
    flat mask in rows of one lane tile."""
    rows = mask.reshape(mask.shape[0], -1)
    if rows.shape[0] < cap:
        flat = mask.reshape(-1)
        rows = jnp.pad(flat, (0, -flat.shape[0] % _TILE)).reshape(-1, _TILE)
    L, W = rows.shape
    cnt = jnp.sum(rows, axis=1, dtype=jnp.int32)
    c = jnp.cumsum(cnt)
    j = jnp.arange(1, cap + 1, dtype=jnp.int32)
    row = jnp.minimum(jnp.searchsorted(c, j, side="left"), L - 1)
    rank = j - (c[row] - cnt[row])
    pre = jnp.cumsum(rows[row], axis=1, dtype=jnp.int32)
    # the rank-th set bit of a row lies behind every prefix below rank
    col = jnp.sum(pre < rank[:, None], axis=1, dtype=jnp.int32)
    return jnp.where(j <= c[-1], row * W + col, -1), c[-1]
