"""Row-granular merge/cover kernels of the staged device constructor.

One jit unit, `merge_cover_rows`, is the whole per-wave compute: gather the
source rows of every group, union-merge them with exact-coverage tracking,
and top-gap cover the result back to the slab width. Both pipeline stages
(the single-shot wave step and every tree-reduction round, see
``tree_merge.py``) are instances of this kernel — they differ only in which
table the group indices point at and in the static working width ``m``.

`_merge_sorted_row` mirrors ``intervals._sweep`` exactly, so a single-shot
merge is bit-identical to the host builder (DESIGN.md §2).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

INVALID = jnp.int32(2**31 - 1)


def slab_bytes(n_rows: int, m: int) -> int:
    """Working-set bytes of one `merge_cover_rows` call: three int32 buffers
    of [n_rows, m] (begins/ends/exact through the sort + scan)."""
    return 3 * 4 * int(n_rows) * int(m)


# ------------------------------------------------------------ row kernels --

def _merge_sorted_row(b, e, x):
    """Union-merge one begin-sorted row of (possibly INVALID) intervals.

    Mirrors intervals._sweep exactly: exact-coverage tracking via
    (ece, holed); touching intervals merge only when type-preserving.
    Returns (ob, oe, ox, count) with merged intervals packed to the front.
    """
    m = b.shape[0]

    def step(carry, i):
        cb, ce, ece, holed, cnt, ob, oe, ox = carry
        bi, ei, xi = b[i], e[i], x[i] != 0
        valid = bi < INVALID
        opened = cnt >= 0          # a current interval exists
        cur_exact = jnp.logical_and(~holed, ece >= ce)

        # decide: merge into current vs flush + open new
        touching = bi == ce + 1
        overlap = bi <= ce
        type_ok = cur_exact == xi
        do_merge = opened & valid & (overlap | (touching & type_ok))
        do_open = valid & ~do_merge

        # --- merge path
        ce_m = jnp.maximum(ce, ei)
        ece_m = jnp.where(xi & (bi <= ece + 1), jnp.maximum(ece, ei), ece)
        holed_m = holed | (xi & (bi > ece + 1))

        # --- flush path (write current interval at slot cnt)
        slot = jnp.maximum(cnt, 0)
        ob_f = ob.at[slot].set(jnp.where(do_open & opened, cb, ob[slot]))
        oe_f = oe.at[slot].set(jnp.where(do_open & opened, ce, oe[slot]))
        ox_f = ox.at[slot].set(jnp.where(do_open & opened,
                                         cur_exact, ox[slot]))
        cnt_new = jnp.where(do_open, jnp.where(opened, cnt + 1, 0), cnt)

        cb_n = jnp.where(do_open, bi, cb)
        ce_n = jnp.where(do_open, ei, jnp.where(do_merge, ce_m, ce))
        ece_n = jnp.where(do_open, jnp.where(xi, ei, bi - 1),
                          jnp.where(do_merge, ece_m, ece))
        # holed only on irreparable exact-coverage gaps (see intervals._sweep)
        holed_n = jnp.where(do_open, False,
                            jnp.where(do_merge, holed_m, holed))
        return (cb_n, ce_n, ece_n, holed_n, cnt_new, ob_f, oe_f, ox_f), None

    init = (jnp.int32(0), jnp.int32(-1), jnp.int32(-2), jnp.bool_(True),
            jnp.int32(-1),
            jnp.full((m,), INVALID, jnp.int32),
            jnp.full((m,), -1, jnp.int32),
            jnp.zeros((m,), jnp.bool_))
    (cb, ce, ece, holed, cnt, ob, oe, ox), _ = jax.lax.scan(
        step, init, jnp.arange(m))
    # final flush
    opened = cnt >= 0
    slot = jnp.maximum(cnt, 0)
    cur_exact = jnp.logical_and(~holed, ece >= ce)
    ob = ob.at[slot].set(jnp.where(opened, cb, ob[slot]))
    oe = oe.at[slot].set(jnp.where(opened, ce, oe[slot]))
    ox = ox.at[slot].set(jnp.where(opened, cur_exact, ox[slot]))
    return ob, oe, ox, cnt + 1


def _topgap_cover_row(ob, oe, ox, cnt, k: int, w_out: int):
    """Top-gap (k-1 largest gaps) cover of a merged row; emit ≤ min(k, w_out)
    intervals into a width-w_out slab. Ties keep the leftmost gap (stable)."""
    m = ob.shape[0]
    idx = jnp.arange(m)
    valid = idx < cnt
    gap_valid = idx + 1 < cnt                       # gap i between I_i, I_{i+1}
    gaps = jnp.where(gap_valid, ob[jnp.minimum(idx + 1, m - 1)] - oe - 1, -1)
    order = jnp.argsort(-gaps, stable=True)
    ranks = jnp.zeros(m, jnp.int32).at[order].set(jnp.arange(m, dtype=jnp.int32))
    keep = (ranks < (k - 1)) & gap_valid
    grp = jnp.concatenate([jnp.zeros(1, jnp.int32),
                           jnp.cumsum(keep.astype(jnp.int32))[:-1]])
    grp = jnp.where(valid, grp, w_out)              # park invalid slots
    nb = jax.ops.segment_min(jnp.where(valid, ob, INVALID), grp,
                             num_segments=w_out + 1)[:w_out]
    ne = jax.ops.segment_max(jnp.where(valid, oe, -1), grp,
                             num_segments=w_out + 1)[:w_out]
    sz = jax.ops.segment_sum(valid.astype(jnp.int32), grp,
                             num_segments=w_out + 1)[:w_out]
    anyx = jax.ops.segment_max(
        jnp.where(valid, ox, False).astype(jnp.int32), grp,
        num_segments=w_out + 1)[:w_out]
    nx = (sz == 1) & (anyx > 0)
    nb = jnp.where(sz > 0, nb, INVALID)
    ne = jnp.where(sz > 0, ne, -1)
    return nb.astype(jnp.int32), ne.astype(jnp.int32), nx, jnp.minimum(cnt, k)


@partial(jax.jit, static_argnames=("k", "w_out", "m", "impl"))
def merge_cover_rows(begins, ends, exact, group_idx, extra_b, extra_e,
                     k: int, w_out: int, m: int, impl: str = "xla"):
    """One batched merge+cover pass over row groups.

    ``begins/ends/exact [T, W]``: the source table (last row must be a
    dummy/empty row used for padding). ``group_idx [B, D]``: per group, the
    D source rows to union (pad slots point at the dummy row).
    ``extra_b/extra_e [B]``: one extra interval per group, concatenated
    FIRST — the node's tree interval in the wave step and in round 1 of a
    tree reduction, INVALID/-1 (absent) elsewhere. The stable begin-sort
    therefore visits equal-begin intervals in the same order as the host
    ``merge_many([tree] + children)`` concat, keeping single-shot merges
    bit-identical to the host sweep.

    ``impl`` selects the merge+cover core: "xla" runs the lax.scan
    reference below; "pallas" runs the fused VMEM-resident kernel
    (`kernels.merge_cover`: compiled on TPU, the Pallas interpreter in the
    CPU tests) — bit-identical by the parity suite, selected via
    ``IndexSpec.kernel_impl``. The gather / concat / sort prologue is
    shared.

    Returns per-group slabs ``[B, w_out]`` covered to ≤ k intervals.
    """
    B, D = group_idx.shape
    W = begins.shape[1]
    cb = begins[group_idx].reshape(B, D * W)
    ce = ends[group_idx].reshape(B, D * W)
    cx = exact[group_idx].reshape(B, D * W)
    cb = jnp.concatenate([extra_b[:, None], cb], axis=1)
    ce = jnp.concatenate([extra_e[:, None], ce], axis=1)
    cx = jnp.concatenate([(extra_b[:, None] < INVALID).astype(cx.dtype), cx],
                         axis=1)
    # pad/truncate to the working width m (callers size m = D*W + 1)
    if cb.shape[1] < m:
        pad = m - cb.shape[1]
        cb = jnp.pad(cb, ((0, 0), (0, pad)), constant_values=INVALID)
        ce = jnp.pad(ce, ((0, 0), (0, pad)), constant_values=-1)
        cx = jnp.pad(cx, ((0, 0), (0, pad)))
    order = jnp.argsort(cb, axis=1, stable=True)
    cb = jnp.take_along_axis(cb, order, 1)
    ce = jnp.take_along_axis(ce, order, 1)
    cx = jnp.take_along_axis(cx, order, 1)

    if impl == "pallas":
        from repro.kernels.merge_cover import merge_cover_sorted_rows
        from repro.kernels.ops import _on_tpu
        return merge_cover_sorted_rows(cb, ce, cx, k=k, w_out=w_out,
                                       interpret=not _on_tpu())

    def row(b, e, x):
        ob, oe, ox, cnt = _merge_sorted_row(b, e, x)
        return _topgap_cover_row(ob, oe, ox, cnt, k, w_out)

    return jax.vmap(row)(cb, ce, cx.astype(jnp.int32))
