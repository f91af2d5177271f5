"""Fused Pallas frontier step for the sparse phase-2 engine.

The XLA loop in `kernels.frontier` pays, per BFS step, a full
``jnp.unique`` sort over the whole candidate matrix (cap·W + Q·m_tail keys,
O(C log C)) plus separate dispatches for the visited test, the classify
gathers and the verdict masking. This module restructures one step into two
VMEM-resident Pallas passes with *bit-identical* state evolution:

  probe    — one kernel over the raw candidate matrix fuses the
             visited-bitset test, the answered-query test, the validity
             mask and the (query, node) key packing into a single pass:
             each lane reads its pre-gathered visited WORD and emits either
             the packed key or SENTINEL. The cross-step dedup therefore
             happens against the bitset *before* any sort, so the sort-
             based compaction below shrinks from C keys to ≤ cap+1.
  compact  — O(C) prefix-sum compaction (XLA cumsum + slot scatter; no
             sort) squeezes the surviving keys into cap+1 slots, then a
             small ``jnp.unique(size=cap+1)`` resolves within-step
             duplicates and restores the sorted order the XLA path
             produces. When the raw survivor count exceeds cap+1 the step
             conservatively raises the overflow flag (the caller's retry is
             sound and unchanged); otherwise the compacted array is
             bit-identical to the XLA path's ``uniq``.
  classify — one kernel over the ≤ cap survivors extends the phase-1
             packed stab kernel (`interval_stab._packed_verdict` — shared,
             not duplicated) with the frontier decisions: the s == t early
             positive, the POS flag and the next-frontier key emit
             (UNKNOWN survivors re-keyed, everything else SENTINEL) all in
             the same VMEM pass.

Row gathers (ELL rows, visited words, meta/slab rows) stay in XLA exactly
as in the phase-1 kernel: XLA emits them as HBM dynamic-gathers and the
kernels stream the gathered slabs through VMEM tiles (see
interval_stab.py). The two index touches remain pluggable — `gather_rows`
and `fetch_rows` — so the same fused loop runs single-device and inside
core.distributed's shard_map (owned-rows gather + psum hooks).

Overflow contract: identical meaning to `kernels.frontier` — positives
found under overflow are sound, the driver retries non-positives with a
larger cap (`DeviceQueryEngine._sparse_driver` is untouched). The only
divergence is that a step whose *raw* survivor count (before within-step
dedup) exceeds cap+1 flags overflow where the XLA path might squeeze under
cap distinct keys; the retry converges to the same answers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref
from .frontier import SENTINEL, _bit, key_bits
from .interval_stab import _packed_verdict

PROBE_BLOCK = 1024


def _probe_kernel(cq_ref, cv_ref, ok_ref, vw_ref, posq_ref, key_ref, *,
                  vbits):
    """Visited-bitset test + key pack, one VMEM pass over candidate lanes.

    vw: the candidate's visited WORD (pre-gathered ``visited[cq, cv>>5]``);
    posq: 1 where the candidate's query is already answered. Emits the
    packed key, or SENTINEL for dead lanes.
    """
    cq = cq_ref[...]
    cv = cv_ref[...]
    # int32 arithmetic shift + &1 still extracts bit (cv&31) exactly,
    # including the sign bit — keeps the kernel free of mixed dtypes
    seen = ((vw_ref[...] >> (cv & 31)) & 1) != 0
    alive = (ok_ref[...] != 0) & ~seen & (posq_ref[...] == 0)
    key_ref[...] = jnp.where(alive, (cq << vbits) | cv,
                             jnp.int32(2**31 - 1))


def _classify_emit_kernel(meta_s_ref, meta_t_ref, slab_ref, key_ref, eq_ref,
                          verdict_ref, front_ref, *, k):
    """Phase-1 packed stab rules + frontier emit, fused on the survivors.

    Extends `_stab_packed_kernel` (shared `_packed_verdict` core) with the
    s == t early positive and the next-frontier decision: UNKNOWN survivors
    re-emit their key, POS/NEG/SENTINEL lanes emit SENTINEL.
    """
    v = _packed_verdict(meta_s_ref[...], meta_t_ref[...], slab_ref[...], k=k)
    v = jnp.where(eq_ref[...] != 0, jnp.int32(ref.POS), v)
    key = key_ref[...]
    valid = key != jnp.int32(2**31 - 1)
    verdict_ref[...] = jnp.where(valid, v, jnp.int32(ref.NEG))
    front_ref[...] = jnp.where(valid & (v == ref.UNKNOWN), key,
                               jnp.int32(2**31 - 1))


def _row_call(kernel, args, *, block, interpret, name):
    """Grid a lane-wise kernel over 1-D int32 operands of equal length;
    ``name`` is the kernel's op name in a device profile."""
    c = args[0].shape[0]
    cp = -(-c // block) * block
    padded = [jnp.pad(a, (0, cp - c))[None, :] for a in args]
    spec = pl.BlockSpec((1, block), lambda i: (0, i))
    out = pl.pallas_call(
        kernel,
        grid=(cp // block,),
        in_specs=[spec] * len(args),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((1, cp), jnp.int32),
        interpret=interpret,
        name=name,
    )(*padded)
    return out[0, :c]


def expand_frontier_loop_fused(ell, tail_src, tail_dst, is_hub, cs, ct,
                               pad, *, n_nodes: int, max_steps: int,
                               cap: int, gather_rows, fetch_rows,
                               post_verdict=None, interpret: bool = False,
                               block: int = PROBE_BLOCK):
    """The fused-step BFS loop; same contract as
    `kernels.frontier.expand_frontier_loop`.

    ``gather_rows(table, ids)`` as in the XLA loop. ``fetch_rows(cands,
    tgts)`` — both GLOBAL node ids, like the XLA loop's ``classify`` —
    returns the classify operands ``(meta_s [C,4], meta_t [C,4],
    slab_s [C,2K])`` for the surviving candidates — a local take on one
    device, an owned-rows gather + psum under the sharded placement.
    ``post_verdict(verdict, cands)`` optionally rewrites verdicts before
    the frontier decision (the dynamic overlay's NEG→UNKNOWN downgrade);
    when set, the next frontier is derived from the rewritten verdicts
    instead of the kernel's fused emit row.
    """
    n, w = n_nodes, ell.shape[1]
    q = cs.shape[0]
    m_t = int(tail_src.shape[0])
    vbits = key_bits(n)
    # same key-space guard as kernels.frontier.expand_frontier_loop
    if vbits > 30:
        raise ValueError(
            f"n_nodes={n} needs {vbits} node bits; packed (query, node) "
            "keys support at most 30 (n < 2**30)")
    assert q <= cap and q < (1 << (31 - vbits)), (
        f"batch of {q} queries exceeds max_batch({n})")
    vmask = jnp.int32((1 << vbits) - 1)
    n_words = (n + 31) // 32

    qi = jnp.arange(q, dtype=jnp.int32)
    front0 = jnp.where(pad, SENTINEL, (qi << vbits) | cs)
    front0 = jnp.concatenate(
        [front0, jnp.full((cap - q,), SENTINEL, jnp.int32)])
    visited0 = jnp.zeros((q, n_words), jnp.uint32).at[qi, cs >> 5].add(
        jnp.where(pad, jnp.uint32(0), _bit(cs)))
    pos0 = jnp.zeros((q,), jnp.bool_)

    probe = functools.partial(_probe_kernel, vbits=vbits)

    def cond(state):
        front, visited, pos, overflow, step = state
        return ((step < max_steps) & ~overflow
                & jnp.any(front != SENTINEL))

    def body(state):
        front, visited, pos, overflow, step = state
        fvalid = front != SENTINEL
        fq = jnp.where(fvalid, front >> vbits, 0)
        fv = jnp.where(fvalid, front & vmask, 0)

        def dedup(cq, cv, ok):
            cq = jnp.where(ok, cq, 0)
            cv = jnp.where(ok, cv, 0)
            # probe: visited/answered tests + key pack in one kernel pass
            # (words pre-gathered in XLA, like the classify slabs)
            keys = _row_call(
                probe,
                (cq, cv, ok.astype(jnp.int32),
                 visited[cq, cv >> 5].view(jnp.int32),
                 pos[cq].astype(jnp.int32)),
                block=block, interpret=interpret, name="frontier_probe")
            # O(C) compaction into cap+1 slots, then a SMALL unique for
            # within-step duplicates; raw > cap+1 is conservative overflow
            emit = keys != SENTINEL
            raw = jnp.sum(emit.astype(jnp.int32))
            slot = jnp.cumsum(emit.astype(jnp.int32)) - 1
            slot = jnp.where(emit & (slot <= cap), slot, cap + 1)  # OOB drop
            compacted = jnp.full((cap + 1,), SENTINEL, jnp.int32
                                 ).at[slot].set(keys, mode="drop")
            return (jnp.unique(compacted, size=cap + 1,
                               fill_value=SENTINEL), raw)

        nbr = gather_rows(ell, fv)                          # [cap, W]
        ell_cq = jnp.broadcast_to(fq[:, None], (cap, w)).reshape(-1)
        ell_cv = nbr.reshape(-1)
        ell_ok = (fvalid[:, None] & (nbr >= 0)).reshape(-1)
        if m_t:
            def with_tail(_):
                fbits = jnp.zeros((q, n_words), jnp.uint32).at[
                    fq, fv >> 5].add(
                        jnp.where(fvalid, _bit(fv), jnp.uint32(0)))
                act = (fbits[:, tail_src >> 5]
                       >> (tail_src & 31).astype(jnp.uint32)[None, :]) & 1
                cq = jnp.concatenate(
                    [ell_cq,
                     jnp.broadcast_to(qi[:, None], (q, m_t)).reshape(-1)])
                cv = jnp.concatenate(
                    [ell_cv,
                     jnp.broadcast_to(tail_dst[None, :],
                                      (q, m_t)).reshape(-1)])
                return dedup(cq, cv,
                             jnp.concatenate([ell_ok,
                                              (act == 1).reshape(-1)]))

            def ell_only(_):
                return dedup(ell_cq, ell_cv, ell_ok)

            uniq, raw = jax.lax.cond(jnp.any(is_hub[fv] & fvalid),
                                     with_tail, ell_only, None)
        else:
            uniq, raw = dedup(ell_cq, ell_cv, ell_ok)
        overflow |= (raw > cap + 1) | (uniq[cap] != SENTINEL)
        new = uniq[:cap]
        nvalid = new != SENTINEL
        nq = jnp.where(nvalid, new >> vbits, 0)
        nv = jnp.where(nvalid, new & vmask, 0)

        nt = ct[nq]                               # target NODE ids
        meta_s, meta_t, slab_s = fetch_rows(nv, nt)
        verdict, fkey = _classify_call(
            meta_s, meta_t, slab_s, new, nv == nt,
            block=block, interpret=interpret)
        if post_verdict is not None:
            v = post_verdict(verdict, nv)
        else:
            v = verdict
        pos = pos.at[nq].max(nvalid & (v == ref.POS))
        visited = visited.at[nq, nv >> 5].add(
            jnp.where(nvalid, _bit(nv), jnp.uint32(0)))
        if post_verdict is not None:
            front = jnp.where(nvalid & (v == ref.UNKNOWN) & ~pos[nq],
                              new, SENTINEL)
        else:
            front = jnp.where(~pos[nq], fkey, SENTINEL)
        return front, visited, pos, overflow, step + 1

    _, _, pos, overflow, _ = jax.lax.while_loop(
        cond, body, (front0, visited0, pos0, jnp.bool_(False), jnp.int32(0)))
    return pos, overflow


def _classify_call(meta_s, meta_t, slab_s, keys, eq, *, block, interpret):
    """pallas_call plumbing of the fused classify+emit kernel: survivors on
    lanes, meta words / slab on sublanes (the phase-1 stab layout)."""
    c = keys.shape[0]
    k2 = slab_s.shape[1]
    cp = -(-c // block) * block

    def pad2(a, fill):
        return jnp.pad(a, ((0, cp - c), (0, 0)), constant_values=fill).T

    def pad1(a):
        return jnp.pad(a, (0, cp - c))[None, :]

    # pad rule as interval_stab: meta_s 1 / meta_t 0 -> NEG; key pad is a
    # real SENTINEL so padded lanes emit SENTINEL
    args = (pad2(meta_s, 1), pad2(meta_t, 0), pad2(slab_s, 0),
            jnp.pad(keys, (0, cp - c), constant_values=2**31 - 1)[None, :],
            pad1(eq.astype(jnp.int32)))
    row = pl.BlockSpec((1, block), lambda i: (0, i))
    verdict, front = pl.pallas_call(
        functools.partial(_classify_emit_kernel, k=k2 // 2),
        grid=(cp // block,),
        in_specs=[pl.BlockSpec((4, block), lambda i: (0, i)),
                  pl.BlockSpec((4, block), lambda i: (0, i)),
                  pl.BlockSpec((k2, block), lambda i: (0, i)),
                  row, row],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((1, cp), jnp.int32)] * 2,
        interpret=interpret,
        name="frontier_classify_emit",
    )(*args)
    return verdict[0, :c], front[0, :c]


@functools.partial(jax.jit,
                   static_argnames=("max_steps", "cap", "interpret"))
def expand_frontier_fused(packed_dev: dict, ell, tail_src, tail_dst,
                          is_hub, cs, ct, pad, *, max_steps: int, cap: int,
                          interpret: bool = False):
    """Single-device fused-step expansion; same contract as
    `kernels.frontier.expand_frontier`. Requires the gather-fused
    slab/meta layout in ``packed_dev`` (see `ops.expand_frontier`, which
    falls back to the XLA loop without it)."""
    meta, slab = packed_dev["meta"], packed_dev["slab"]

    def fetch_rows(cands, tgts):
        return meta[cands], meta[tgts], slab[cands]

    return expand_frontier_loop_fused(
        ell, tail_src, tail_dst, is_hub, cs, ct, pad,
        n_nodes=ell.shape[0], max_steps=max_steps, cap=cap,
        gather_rows=lambda table, ids: table[ids],
        fetch_rows=fetch_rows, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("max_steps", "cap", "interpret"))
def expand_frontier_overlay_fused(packed_dev: dict, ell, tail_src,
                                  tail_dst, is_hub, can_reach_tail, cs, ct,
                                  pad, *, max_steps: int, cap: int,
                                  interpret: bool = False):
    """Fused-step union-graph expansion (live-update overlay); same
    contract as `kernels.frontier.expand_frontier_overlay`."""
    meta, slab = packed_dev["meta"], packed_dev["slab"]

    def fetch_rows(cands, tgts):
        return meta[cands], meta[tgts], slab[cands]

    def post_verdict(v, cands):
        return jnp.where((v == ref.NEG) & can_reach_tail[cands],
                         jnp.int32(ref.UNKNOWN), v)

    return expand_frontier_loop_fused(
        ell, tail_src, tail_dst, is_hub, cs, ct, pad,
        n_nodes=ell.shape[0], max_steps=max_steps, cap=cap,
        gather_rows=lambda table, ids: table[ids],
        fetch_rows=fetch_rows, post_verdict=post_verdict,
        interpret=interpret)
