"""Public jit'd wrappers around the Pallas kernels.

The kernels compile for TPU. Off-TPU each op runs them in the Pallas
interpreter (``interpret=True``) — the CPU test path; a deployment on a
chip asserts the backend up front (``chip_smoke.py``) so the interpreter is
never taken there in silence. The wrappers also do the layout prep the
kernels expect, and are the ONLY entry points the rest of the system uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .batched_mp import batched_mp as _batched_mp
from .frontier import expand_frontier as _expand_frontier
from .frontier import expand_frontier_overlay as _expand_frontier_overlay
from .frontier import max_batch as frontier_max_batch  # noqa: F401 (re-export)
from .frontier_fused import expand_frontier_fused as _expand_frontier_fused
from .frontier_fused import (
    expand_frontier_overlay_fused as _expand_frontier_overlay_fused)
from .flash_attention import flash_attention as _flash
from .interval_stab import interval_stab_classify as _stab
from .interval_stab import interval_stab_classify_packed as _stab_packed
from .retrieval_score import retrieval_score as _retrieval_score

NEG, POS, UNKNOWN = ref.NEG, ref.POS, ref.UNKNOWN

KERNEL_IMPLS = ("xla", "pallas", "auto")


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_kernel_impl(impl: str) -> str:
    """Resolve the ``IndexSpec.kernel_impl`` knob to a concrete core.

    "xla"/"pallas" are explicit; "auto" picks the fused Pallas kernels on
    TPU — the only backend they compile for — and the XLA reference path
    everywhere else. Explicit "pallas" off-TPU runs the kernels in the
    Pallas interpreter, which is how the tests exercise them on the CPU.
    """
    if impl not in KERNEL_IMPLS:
        raise ValueError(
            f"kernel_impl must be one of {KERNEL_IMPLS}, got {impl!r}")
    if impl == "auto":
        return "pallas" if _on_tpu() else "xla"
    return impl


def attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
              use_pallas: bool = True):
    """Flash attention. q: [B,Sq,H,hd]; k, v: [B,Sk,H,hd] (GQA expanded).

    TPU: the Pallas flash kernel (O(S·hd) HBM traffic). Elsewhere /
    use_pallas=False: the f32 softmax oracle.
    """
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal,
                                       q_offset=q_offset)
    return _flash(q, k, v, causal=causal, q_offset=q_offset,
                  interpret=not _on_tpu())


def classify_queries(packed_dev: dict, cs, ct, *, use_pallas: bool = True,
                     block_q: int = 1024):
    """Phase-1 classification of condensed-id query pairs (cs, ct).

    ``packed_dev``: dict from PackedIndex.to_device(). Uses the gather-fused
    slab/meta layout when present (§Perf iteration F1: 3 gathers instead of
    12, exact flags riding the sign bit of begins); falls back to the naive
    12-array layout otherwise. Returns verdict [Q] int32; the [cs == ct]
    early positive is applied here.
    """
    if not use_pallas and not packed_dev.get("_prefetched"):
        # shared pure-jnp dispatch — the same rules the sparse phase-2
        # frontier loop classifies with (kernels.ref)
        return ref.classify_packed_dev_ref(packed_dev, cs, ct)
    if packed_dev.get("_prefetched") or "slab" in packed_dev:
        if packed_dev.get("_prefetched"):
            # rows already exchanged (core.distributed sharded placement)
            meta_s = packed_dev["meta_s"]
            meta_t = packed_dev["meta_t"]
            slab_s = packed_dev["slab_s"]
        else:
            meta, slab = packed_dev["meta"], packed_dev["slab"]
            meta_s, meta_t, slab_s = meta[cs], meta[ct], slab[cs]
        if use_pallas:
            verdict = _stab_packed(meta_s, meta_t, slab_s, block_q=block_q,
                                   interpret=not _on_tpu())
        else:
            verdict = ref.interval_stab_classify_packed_ref(
                meta_s, meta_t, slab_s)
        return jnp.where(cs == ct, POS, verdict)
    pi = packed_dev["pi"]
    tau = packed_dev["tau"]
    lvl = packed_dev["blevel"]
    begins = packed_dev["begins"]
    ends = packed_dev["ends"]
    exact = packed_dev["exact"]
    if "s_plus" in packed_dev:
        sp, sm = packed_dev["s_plus"], packed_dev["s_minus"]
    else:
        n = pi.shape[0]
        sp = jnp.zeros((n, 1), dtype=jnp.uint32)
        sm = sp
    args = (pi[ct], tau[cs], tau[ct], lvl[cs], lvl[ct],
            begins[cs], ends[cs], exact[cs],
            sp[cs], sm[cs], sp[ct], sm[ct])
    if use_pallas:
        verdict = _stab(*args, block_q=block_q, interpret=not _on_tpu())
    else:
        verdict = ref.interval_stab_classify_ref(*args)
    return jnp.where(cs == ct, POS, verdict)


def classify_all_nodes_vs_target(packed_dev: dict, ct, *, node_chunk=None,
                                 can_reach_tail=None):
    """Vectorized phase-2 helper: classify EVERY node u against target ct:
    returns (expandable [Q, n] bool, definite_pos [Q, n] bool).

    expandable(u) = u has an approximate hit and passes all negative filters
    (worth traversing); definite_pos(u) = reaching u proves the query
    (exact hit, seed-positive, or u == ct). ``can_reach_tail`` ([n] bool,
    reach.dynamic overlay serving) keeps base-NEG nodes expandable while
    they can still reach a delta-edge tail — the dense-mode analogue of the
    sparse engine's overlay classify.
    """
    pi = packed_dev["pi"]
    n = pi.shape[0]
    cs_all = jnp.arange(n, dtype=jnp.int32)
    def one(ct_scalar):
        v = classify_queries(packed_dev,
                             cs_all, jnp.full((n,), ct_scalar, jnp.int32),
                             use_pallas=False)
        return v
    v = jax.vmap(one)(ct)                     # [Q, n]
    expandable = v == UNKNOWN
    if can_reach_tail is not None:
        expandable |= (v == NEG) & can_reach_tail[None, :]
    return expandable, v == POS


def expand_frontier(packed_dev: dict, ell, tail_src, tail_dst, is_hub,
                    cs, ct, pad, *, max_steps: int, cap: int,
                    kernel_impl: str = "xla"):
    """Sparse phase-2 engine: batched guided BFS over the ELL + tail layout
    (kernels.frontier). cs/ct: [Q] condensed ids of UNKNOWN queries; pad
    marks batch-padding slots; is_hub gates the tail sweep per step.
    Returns (pos [Q] bool, overflow bool) — under overflow, positives are
    sound and the caller retries the rest with a larger cap. Chunk size is
    bounded by ``frontier_max_batch(n)``.

    ``kernel_impl`` (resolved — "xla" or "pallas") selects the step core:
    "pallas" runs the fused probe/classify step of kernels.frontier_fused,
    which needs the gather-fused slab/meta layout; without it the call
    falls back to the XLA loop (same answers by the parity suite).
    """
    if kernel_impl == "pallas" and "slab" in packed_dev:
        return _expand_frontier_fused(
            packed_dev, ell, tail_src, tail_dst, is_hub, cs, ct, pad,
            max_steps=max_steps, cap=cap, interpret=not _on_tpu())
    return _expand_frontier(packed_dev, ell, tail_src, tail_dst, is_hub,
                            cs, ct, pad, max_steps=max_steps, cap=cap)


def expand_frontier_overlay(packed_dev: dict, ell, tail_src, tail_dst,
                            is_hub, can_reach_tail, cs, ct, pad, *,
                            max_steps: int, cap: int,
                            kernel_impl: str = "xla"):
    """Union-graph (base + delta slab) frontier expansion for live-update
    serving (kernels.frontier / reach.dynamic, DESIGN.md §6). Interface as
    ``expand_frontier`` plus ``can_reach_tail`` [n] bool; ``max_steps``
    must bound the union BFS depth (callers pass n — delta edges can form
    cycles over the base DAG)."""
    if kernel_impl == "pallas" and "slab" in packed_dev:
        return _expand_frontier_overlay_fused(
            packed_dev, ell, tail_src, tail_dst, is_hub, can_reach_tail,
            cs, ct, pad, max_steps=max_steps, cap=cap,
            interpret=not _on_tpu())
    return _expand_frontier_overlay(
        packed_dev, ell, tail_src, tail_dst, is_hub, can_reach_tail,
        cs, ct, pad, max_steps=max_steps, cap=cap)


def batched_mp(adj, x, w, *, use_pallas: bool = True):
    """Dense per-graph message passing: [B,N,N]x[B,N,F]x[F,H] -> [B,N,H]."""
    if not use_pallas:
        return ref.batched_mp_ref(adj, x, w)
    return _batched_mp(adj, x, w, interpret=not _on_tpu())


def retrieval_score(cands, interests, *, use_pallas: bool = True):
    """MIND retrieval: max-over-interest dot scores, [C,D]x[I,D] -> [C]."""
    if not use_pallas:
        return ref.retrieval_score_ref(cands, interests)
    return _retrieval_score(cands, interests, interpret=not _on_tpu())


# ------------------------------------------------------------------ jnp ops
# Substrate ops the spec calls out as part of the system (no native JAX op):

def segment_mp(x_src, dst_ids, n_nodes, reduce: str = "sum"):
    """Message passing via edge-gather + segment reduction.

    x_src: [m, F] gathered source features; dst_ids: [m] targets.
    """
    if reduce == "sum":
        return jax.ops.segment_sum(x_src, dst_ids, num_segments=n_nodes)
    if reduce == "max":
        return jax.ops.segment_max(x_src, dst_ids, num_segments=n_nodes)
    if reduce == "mean":
        s = jax.ops.segment_sum(x_src, dst_ids, num_segments=n_nodes)
        c = jax.ops.segment_sum(jnp.ones((x_src.shape[0], 1), x_src.dtype),
                                dst_ids, num_segments=n_nodes)
        return s / jnp.maximum(c, 1.0)
    raise ValueError(reduce)


def embedding_bag(table, ids, bag_ids, n_bags, weights=None, mode="sum"):
    """EmbeddingBag: gather rows + segment-reduce into bags.

    table: [V, D]; ids: [L] flat item ids; bag_ids: [L] bag assignment.
    """
    rows = jnp.take(table, ids, axis=0)
    if weights is not None:
        rows = rows * weights[:, None]
    if mode == "sum":
        return jax.ops.segment_sum(rows, bag_ids, num_segments=n_bags)
    if mode == "mean":
        s = jax.ops.segment_sum(rows, bag_ids, num_segments=n_bags)
        c = jax.ops.segment_sum(jnp.ones((ids.shape[0], 1), rows.dtype),
                                bag_ids, num_segments=n_bags)
        return s / jnp.maximum(c, 1.0)
    raise ValueError(mode)
