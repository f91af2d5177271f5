"""Pallas TPU kernel: fused merge + top-gap cover of sorted interval rows.

The wavefront builder's per-wave compute (`core.build.merge_kernels.
merge_cover_rows`) union-merges each group's begin-sorted interval slab and
re-covers it to the budget width. The XLA reference path runs the merge as a
`lax.scan` over the ``m`` sorted slots — per step it rewrites three ``[m]``
carry buffers, so one wave moves O(m²) bytes per row through HBM and the
cover's gap ranking pays a second full argsort. This kernel keeps the whole
row resident in VMEM and makes both phases one pass:

  pass 1 (sequential over the m sorted slots, vectorized over BLOCK_B rows
  on the 128-wide lane dim): the union-merge recurrence with exact-coverage
  tracking — identical update rules to ``_merge_sorted_row`` — but instead
  of compacting merged intervals with per-lane dynamic scatters (unsupported
  on the VPU), it stores four O(1) per-slot words into VMEM scratch: the
  running group begin/end, the group-open flag, and the would-be exact flag.
  Merged intervals stay *in place*: because INVALID begins sort to the tail,
  valid slots form a prefix and every merged interval is the contiguous run
  of slots between two open flags.

  pass 2 (vectorized): group boundaries come from the open/valid flags, the
  inter-group gaps from the shifted begins, the top-(k-1) gap selection from
  k-1 masked argmax rounds (ties keep the leftmost row — the same order as
  the reference's stable argsort), the output-group ids from a log-step
  Hillis-Steele prefix sum, and the final ≤ w_out covered intervals from
  per-output masked min/max/any reductions over the slot axis.

Grid: 1-D over row tiles of BLOCK_B lanes; `tree_merge.py`'s constant-width
chunks map 1:1 onto grid tiles. VMEM per tile is about 20 int32 planes of
[m, BLOCK_B] — double-buffered input slabs, 4 scratch planes and pass 2's
temporaries: the TPU compiler asks 19.75 MiB at the widest single-shot
width m = 2049 and BLOCK_B = 128, above Mosaic's 16 MiB default scoped
limit, so the call requests its own (`vmem_limit_bytes`). Mosaic cannot
carry or select i1 vectors, so loop carries and shifted planes are int32
0/1; bools stay local to one expression. Bit-identical to the XLA path by
construction; asserted in tests/test_merge_cover_kernel.py, and compiled
for a v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# plain int (not jnp.int32): a module-level jax scalar would be captured as
# a constant by the kernel trace, which pallas_call rejects
INVALID = 2**31 - 1
DEFAULT_BLOCK_B = 128
# Mosaic's default scoped-VMEM limit (16 MiB) is below what pass 2 needs at
# the widest single-shot width; the kernel asks for room by plane count
_VMEM_PLANES = 32
_VMEM_FLOOR = 16 << 20
_VMEM_CEIL = 100 << 20          # of v5e's 128 MiB


def vmem_limit_bytes(m: int, block_b: int) -> int:
    """Scoped-VMEM request for one tile: room for ``_VMEM_PLANES`` int32
    [m, block_b] planes (double-buffered input slabs, the four scratch
    planes and pass 2's temporaries — the TPU compiler measured ~20 at
    m = 2049), clamped to [16 MiB, 100 MiB]."""
    need = _VMEM_PLANES * m * block_b * 4
    return int(min(max(need, _VMEM_FLOOR), _VMEM_CEIL))


def _merge_cover_kernel(b_ref, e_ref, x_ref,
                        nb_ref, ne_ref, nx_ref, cnt_ref,
                        cb_s, ce_s, ex_s, op_s, *, k, w_out, m):
    bq = b_ref.shape[1]

    # ---- pass 1: union-merge recurrence (sequential over the m slots) ----
    # holed/opened ride the loop as int32 0/1 planes: Mosaic cannot carry
    # i1 vectors through a loop (it refuses the i8 -> i1 truncation), so
    # every bool stays local to one step
    def step(i, carry):
        cb, ce, ece, holed_i, opened_i = carry
        holed = holed_i != 0
        idx = pl.ds(i, 1)
        bi = b_ref[idx, :]
        ei = e_ref[idx, :]
        xi = x_ref[idx, :] != 0
        valid = bi < INVALID
        cur_exact = (~holed) & (ece >= ce)

        touching = bi == ce + 1
        overlap = bi <= ce
        type_ok = cur_exact == xi
        do_merge = (opened_i != 0) & valid & (overlap | (touching & type_ok))
        do_open = valid & ~do_merge

        ce_m = jnp.maximum(ce, ei)
        ece_m = jnp.where(xi & (bi <= ece + 1), jnp.maximum(ece, ei), ece)
        holed_m = holed | (xi & (bi > ece + 1))

        cb_n = jnp.where(do_open, bi, cb)
        ce_n = jnp.where(do_open, ei, jnp.where(do_merge, ce_m, ce))
        ece_n = jnp.where(do_open, jnp.where(xi, ei, bi - 1),
                          jnp.where(do_merge, ece_m, ece))
        # bool-valued select spelled as logic (an i1 select does not lower)
        holed_n = (~do_open) & ((do_merge & holed_m) | (~do_merge & holed))
        exf = (~holed_n) & (ece_n >= ce_n)   # exact flag if closed after i

        cb_s[idx, :] = cb_n
        ce_s[idx, :] = ce_n
        ex_s[idx, :] = exf.astype(jnp.int32)
        op_s[idx, :] = do_open.astype(jnp.int32)
        return (cb_n, ce_n, ece_n, holed_n.astype(jnp.int32),
                opened_i | valid.astype(jnp.int32))

    init = (jnp.zeros((1, bq), jnp.int32),
            jnp.full((1, bq), -1, jnp.int32),
            jnp.full((1, bq), -2, jnp.int32),
            jnp.ones((1, bq), jnp.int32),
            jnp.zeros((1, bq), jnp.int32))
    jax.lax.fori_loop(0, m, step, init)

    # ---- pass 2: top-gap cover over the in-place merged groups ----------
    # slot shifts run on the int32 planes: Mosaic cannot concatenate i1
    b = b_ref[...]
    valid = b < INVALID                       # valid slots form a prefix
    op_i = op_s[...]
    opn = op_i != 0
    cbm = cb_s[...]
    cem = ce_s[...]
    exm = ex_s[...] != 0

    open_next = jnp.concatenate(
        [op_i[1:], jnp.zeros((1, bq), jnp.int32)], axis=0) != 0
    b_next = jnp.concatenate(
        [b[1:], jnp.full((1, bq), INVALID, jnp.int32)], axis=0)
    valid_next = b_next < INVALID
    is_last = valid & (open_next | ~valid_next)

    # gap between a group and its successor lives on the group's last slot
    gap = jnp.where(is_last & valid_next, b_next - cem - 1, -1)

    # keep the k-1 largest gaps; ties pick the smallest slot — the exact
    # set the reference's stable argsort(-gaps) rank < k-1 keeps
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, bq), 0)
    keep = jnp.zeros((m, bq), jnp.int32)      # 0/1 plane of kept cuts
    gw = gap
    for _ in range(k - 1):
        mx = jnp.max(gw, axis=0, keepdims=True)
        cand = (gw == mx) & (mx > -1)
        selrow = jnp.min(jnp.where(cand, rows, m), axis=0, keepdims=True)
        sel = rows == selrow
        keep = jnp.where(sel, 1, keep)
        gw = jnp.where(sel, -2, gw)

    # output-group id = exclusive prefix count of kept cuts above each slot
    c = keep
    sh = 1
    while sh < m:
        c = c + jnp.concatenate(
            [jnp.zeros((sh, bq), jnp.int32), c[:-sh]], axis=0)
        sh *= 2
    out_id = c - keep                         # exclusive

    last_x = (is_last & exm).astype(jnp.int32)
    for j in range(w_out):
        mj = valid & (out_id == j)
        nbj = jnp.min(jnp.where(mj, cbm, INVALID), axis=0, keepdims=True)
        nej = jnp.max(jnp.where(mj, cem, -1), axis=0, keepdims=True)
        szj = jnp.sum(jnp.where(mj, op_i, 0), axis=0, keepdims=True)
        anyx = jnp.max(jnp.where(mj, last_x, 0), axis=0, keepdims=True)
        nb_ref[j:j + 1, :] = jnp.where(szj > 0, nbj, INVALID)
        ne_ref[j:j + 1, :] = jnp.where(szj > 0, nej, -1)
        nx_ref[j:j + 1, :] = jnp.where(szj == 1, anyx, 0)

    cnt = jnp.sum(opn.astype(jnp.int32), axis=0, keepdims=True)
    cnt_ref[...] = jnp.minimum(cnt, k)


@functools.partial(jax.jit,
                   static_argnames=("k", "w_out", "block_b", "interpret"))
def merge_cover_sorted_rows(cb, ce, cx, *, k: int, w_out: int,
                            block_b: int = DEFAULT_BLOCK_B,
                            interpret: bool = False):
    """Fused merge + cover of begin-sorted rows.

    cb/ce/cx: [B, m] int32, sorted by cb per row (INVALID-padded tails).
    Returns (nb [B, w_out] int32, ne [B, w_out] int32, nx [B, w_out] bool,
    cnt [B] int32) — bit-identical to the vmapped
    ``_merge_sorted_row`` + ``_topgap_cover_row`` reference.
    """
    B, m = cb.shape
    bp = -(-B // block_b) * block_b

    def prep(a, fill):
        return jnp.pad(a, ((0, bp - B), (0, 0)), constant_values=fill).T

    # padded lanes hold zero valid intervals -> cnt 0, INVALID slabs
    args = (prep(cb, INVALID), prep(ce, -1), prep(cx.astype(jnp.int32), 0))
    grid = (bp // block_b,)
    slab_spec = pl.BlockSpec((m, block_b), lambda i: (0, i))
    out_spec = pl.BlockSpec((w_out, block_b), lambda i: (0, i))
    row_spec = pl.BlockSpec((1, block_b), lambda i: (0, i))
    nb, ne, nx, cnt = pl.pallas_call(
        functools.partial(_merge_cover_kernel, k=k, w_out=w_out, m=m),
        grid=grid,
        in_specs=[slab_spec] * 3,
        out_specs=[out_spec] * 3 + [row_spec],
        out_shape=[jax.ShapeDtypeStruct((w_out, bp), jnp.int32)] * 3
        + [jax.ShapeDtypeStruct((1, bp), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((m, block_b), jnp.int32)] * 4,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(m, block_b)),
        interpret=interpret,
        name="merge_cover",
    )(*args)
    return nb.T[:B], ne.T[:B], nx.T[:B] != 0, cnt[0, :B]
