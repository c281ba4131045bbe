"""Gap closing (paper §III-D) + scaffold sequence rendering.

Each gap between adjacent scaffold members is attacked with the localized
mer-walk from local_assembly (HipMer's "spanning k-mer walk" closure
method): walk rightward from the left contig's inward-facing end, using
reads localized to either flanking contig, and check whether the walk
reaches the right contig's leading k-mer.  Flanks that already overlap
(a negative gap estimate) are joined on the overlap, which may differ in a
read error or two; the other unclosed gaps render as N runs sized by the
link's gap estimate.

Load-balance adaptation: HipMer round-robins gaps across processors
because closure costs vary wildly; the vectorized lockstep walk makes every
gap a SIMD lane, which is the degenerate (and optimal) case of that
round-robin (DESIGN.md §2).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops

from . import kmer, local_assembly
from .types import ContigSet, ReadSet
from .scaffolding import Scaffolds

NONE = jnp.int32(-1)
# flank overlaps joined without a walk: up to k - 1 plus two local assembly
# extensions of max_ext = 64 bases long, differing in at most a couple of
# read errors (the right flank's bases are rendered there)
MAX_OVERLAP = 256
MAX_OVERLAP_MISMATCHES = 2
# scaffold rows rendered per step (bounds the render's temporaries)
RENDER_ROWS = 256


class ScaffoldSeqs(NamedTuple):
    bases: jnp.ndarray    # [S, Lmax] uint8 (4 = pad / N)
    lengths: jnp.ndarray  # [S] int32
    closed: jnp.ndarray   # [S, M] bool gap after member j was walk-closed
    #                       or its flanks overlap (no N run)
    n_scaffolds: jnp.ndarray


def _member_window(contigs: ContigSet, cid, orient, start, width: int):
    """Oriented bases [start, start + width) of scaffold members (4 outside
    the member), and the members' lengths.  One 2-D gather of just the
    window, never a copy of whole contig rows."""
    c = jnp.clip(cid, 0)
    length = jnp.where(cid >= 0, contigs.lengths[c], 0)
    q = start[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    # rc: base q = complement(base[len-1-q])
    src = jnp.where(orient[:, None] == 0, q, length[:, None] - 1 - q)
    b = contigs.bases[c[:, None], jnp.clip(src, 0, contigs.max_len - 1)]
    b = jnp.where(orient[:, None] == 0, b, kmer.complement_base(b))
    inside = (q >= 0) & (q < length[:, None])
    return jnp.where(inside, b, 4).astype(jnp.uint8), length


def _overlaps(contigs: ContigSet, lc, lo_, rc_, ro, *, min_overlap: int):
    """Length of the suffix/prefix overlap of each gap's flanks.

    Adjacent contigs overlap by up to k - 1 bases plus what local assembly
    walked onto both ends, and the link's gap estimate is then negative.
    A contig end can carry a read error, so an overlap may differ in up to
    `MAX_OVERLAP_MISMATCHES` bases.  Seed filter: every overlap length o
    up to `MAX_OVERLAP` whose first or last `min_overlap` bases match
    exactly; the longest candidate must then match over its whole length
    but for those mismatches.  Returns 0 where no such overlap exists.
    """
    W, m = MAX_OVERLAP, min_overlap
    llen = jnp.where(lc >= 0, contigs.lengths[jnp.clip(lc, 0)], 0)
    tail, _ = _member_window(contigs, lc, lo_, llen - W, W)
    head, rlen = _member_window(contigs, rc_, ro, jnp.zeros_like(rc_), W)
    # candidate p: the overlap starts at tail[p], o = W - p bases long
    n_start = W - m + 1
    seed = jnp.ones((lc.shape[0], n_start), bool)   # head[:m] at tail[p]
    back = jnp.ones((lc.shape[0], n_start), bool)   # tail[-m:] at head[o-m]
    for i in range(m):
        seed &= tail[:, i : i + n_start] == head[:, i : i + 1]
        back &= head[:, i : i + n_start][:, ::-1] == tail[:, W - m + i :
                                                           W - m + i + 1]
    seed |= back
    p = jnp.argmax(seed, axis=1).astype(jnp.int32)   # longest candidate
    o = W - p
    i = jnp.arange(W, dtype=jnp.int32)[None, :]
    tail_at = jnp.take_along_axis(
        tail, jnp.clip(p[:, None] + i, 0, W - 1), axis=1
    )
    mismatches = ((i < o[:, None]) & (tail_at != head)).sum(axis=1)
    ok = (seed.any(axis=1) & (mismatches <= MAX_OVERLAP_MISMATCHES)
          & (o <= llen) & (o <= rlen))
    return jnp.where(ok, o, 0)


def _gap_walks(
    wt: local_assembly.WalkTables,
    mer_sizes: tuple,
    tag_bits: int,
    left_tail_hi,
    left_tail_lo,
    left_contig,
    target_hi,
    target_lo,
    active,
    *,
    seed_len: int,
    max_walk: int,
    backend=None,
):
    """Walk from each gap's left flank; stop when the target k-mer of the
    right flank is produced.  Returns (walk, hit, hit_pos).

    The target check runs INSIDE the fused walk kernel (`ops.mer_walk`
    with seed_len > 0, DESIGN.md §8): after each accepted base the
    seed_len-suffix of the walk buffer is compared against the target, and
    a matching walker halts with status HIT at hit_pos accepted bases —
    the same first-match position the historical post-walk scan found.
    """
    out = ops.mer_walk(
        wt,
        left_tail_hi,
        left_tail_lo,
        left_contig,
        active,
        mer_sizes=tuple(mer_sizes),
        tag_bits=tag_bits,
        max_ext=max_walk,
        target_hi=target_hi,
        target_lo=target_lo,
        seed_len=seed_len,
        backend=backend,
    )
    walk = local_assembly.WalkResult(
        ext_bases=out.ext_bases, ext_len=out.ext_len, status=out.status
    )
    return walk, out.hit, out.hit_pos


def close_and_render(
    scaffs: Scaffolds,
    contigs: ContigSet,
    reads: ReadSet,
    aln_contig,
    *,
    seed_len: int = 17,
    mer_sizes: tuple = (17, 21, 25),
    walk_capacity: int = 1 << 16,
    max_walk: int = 64,
    max_scaffold_len: int = 1 << 13,
    max_n_run: int = 64,
    backend=None,
) -> ScaffoldSeqs:
    """Close gaps where possible, then render scaffold sequences."""
    tag_bits = min(16, 62 - 2 * max(mer_sizes))
    read_contig = local_assembly.localize_reads(reads, aln_contig)
    wt = local_assembly.build_walk_tables(
        reads, read_contig, mer_sizes=mer_sizes, tag_bits=tag_bits,
        capacity=walk_capacity, backend=backend,
    )
    return close_and_render_with_tables(
        scaffs, contigs, wt, seed_len=seed_len, mer_sizes=mer_sizes,
        max_walk=max_walk, max_scaffold_len=max_scaffold_len,
        max_n_run=max_n_run, backend=backend,
    )


def close_and_render_with_tables(
    scaffs: Scaffolds,
    contigs: ContigSet,
    wt: local_assembly.WalkTables,
    *,
    seed_len: int = 17,
    mer_sizes: tuple = (17, 21, 25),
    max_walk: int = 64,
    max_scaffold_len: int = 1 << 13,
    max_n_run: int = 64,
    backend=None,
) -> ScaffoldSeqs:
    """Gap closure from prebuilt walk tables (streaming ingest accumulates
    them batch by batch, DESIGN.md §7; the in-memory wrapper above builds
    them from the whole read set in one shot).

    A gap the walk does not close but whose flanks overlap (by `seed_len`
    to `MAX_OVERLAP` bases; see `_overlaps`) renders the overlap once; any
    other open gap renders as an N run sized by the gap estimate."""
    return _close_and_render(
        scaffs, contigs, wt, seed_len=seed_len, mer_sizes=tuple(mer_sizes),
        max_walk=max_walk, max_scaffold_len=max_scaffold_len,
        max_n_run=max_n_run, backend=ops.resolve_backend(backend),
    )


@functools.partial(jax.jit, static_argnames=(
    "seed_len", "mer_sizes", "max_walk", "max_scaffold_len", "max_n_run",
    "backend"))
def _close_and_render(scaffs, contigs, wt, *, seed_len: int, mer_sizes: tuple,
                      max_walk: int, max_scaffold_len: int, max_n_run: int,
                      backend: str) -> ScaffoldSeqs:
    S, M = scaffs.contig.shape
    C = contigs.capacity
    tag_bits = min(16, 62 - 2 * max(mer_sizes))
    # per (scaffold, j) gap: left member j, right member j+1
    left_c = scaffs.contig
    left_o = scaffs.orient
    right_c = jnp.concatenate([scaffs.contig[:, 1:], jnp.full((S, 1), NONE)], axis=1)
    right_o = jnp.concatenate(
        [scaffs.orient[:, 1:], jnp.zeros((S, 1), jnp.uint8)], axis=1
    )
    gap_active = (left_c >= 0) & (right_c >= 0)
    flat = lambda x: x.reshape((-1,))
    lc, lo_, rc_, ro = map(flat, (left_c, left_o, right_c, right_o))
    g_active = flat(gap_active)
    # left flank inward-facing suffix buffer (oriented reading frame)
    bhi, blo, _ = local_assembly.contig_end_buffers(
        contigs, jnp.ones((C,), bool)
    )
    # member oriented fwd (o=0): inward end = right end -> suffix buffer (C:)
    # member oriented rc  (o=1): inward end = left end -> rc'd prefix ([:C])
    lsel = jnp.clip(lc, 0)
    tail_hi = jnp.where(lo_ == 0, bhi[C:][lsel], bhi[:C][lsel])
    tail_lo = jnp.where(lo_ == 0, blo[C:][lsel], blo[:C][lsel])
    # target: right member's leading seed k-mer in scaffold orientation
    rhead, _ = _member_window(contigs, rc_, ro, jnp.zeros_like(rc_), seed_len)
    t_hi, t_lo = kmer.pack_window(rhead, k=seed_len)
    walk, hit, hit_pos = _gap_walks(
        wt,
        mer_sizes=tuple(mer_sizes),
        tag_bits=tag_bits,
        left_tail_hi=tail_hi,
        left_tail_lo=tail_lo,
        left_contig=jnp.clip(lc, 0),
        target_hi=t_hi,
        target_lo=t_lo,
        active=g_active,
        seed_len=seed_len,
        max_walk=max_walk,
        backend=backend,
    )
    # closure bases: the walked bases minus the trailing seed overlap
    fill_len = jnp.where(hit, jnp.clip(hit_pos - seed_len, 0), NONE)  # -1: open
    overlap = _overlaps(contigs, lc, lo_, rc_, ro, min_overlap=seed_len)
    overlap = jnp.where(g_active & (fill_len < 0), overlap, 0).reshape(S, M)
    # ---- render ----
    est_gap = jnp.clip(scaffs.gap, 1.0, float(max_n_run)).astype(jnp.int32)
    gap_len = jnp.where(
        fill_len.reshape(S, M) >= 0, fill_len.reshape(S, M),
        jnp.where(overlap > 0, -overlap, jnp.where(gap_active, est_gap, 0)),
    )
    out, lengths = _render(
        scaffs.contig, scaffs.orient, scaffs.n_members, gap_len,
        jnp.clip(fill_len, 0).reshape(S, M), walk.ext_bases, contigs,
        max_scaffold_len=max_scaffold_len,
    )
    return ScaffoldSeqs(
        bases=out,
        lengths=lengths,
        closed=(fill_len.reshape(S, M) >= 0) | (overlap > 0),
        n_scaffolds=scaffs.n_scaffolds,
    )


@functools.partial(jax.jit, static_argnames=("max_scaffold_len",))
def _render(member, orient, n_members, gap_len, walk_len, walk_bases,
            contigs: ContigSet, *, max_scaffold_len: int):
    """Scaffold rows: each member's oriented bases at its offset, then the
    walked closure bases of the gap after it, else N.

    Every output position takes its bases from the last member that starts
    at or before it: members fill a prefix of each row and their offsets
    never decrease (an overlap is at most the right member's length), so
    where flanks overlap the right flank's bases win.  One gather per
    position; nothing is scattered.  Rows go RENDER_ROWS at a time, so the
    per-position temporaries stay a small multiple of that block."""
    S, M = member.shape
    Lc = contigs.max_len
    valid = member >= 0
    c = jnp.clip(member, 0)
    lens = jnp.where(valid, contigs.lengths[c], 0)
    step = lens + gap_len
    offsets = jnp.concatenate(
        [jnp.zeros((S, 1), jnp.int32), jnp.cumsum(step, axis=1)[:, :-1]], axis=1
    )
    total = jnp.max(jnp.where(valid, offsets + lens, 0), axis=1)
    pos = jnp.arange(max_scaffold_len, dtype=jnp.int32)[None, :]
    W = walk_bases.shape[1]

    def rows(blk):
        valid, offsets, lens, c, orient, walk_len, walk_bases = blk
        B = valid.shape[0]
        # the covering member's fields, selected member by member (M is
        # small)
        j = jnp.full((B, max_scaffold_len), -1, jnp.int32)
        off = ml = cid = ori = wl = jnp.zeros((B, max_scaffold_len), jnp.int32)
        for m in range(M):
            here = valid[:, m : m + 1] & (offsets[:, m : m + 1] <= pos)
            j = jnp.where(here, m, j)
            off = jnp.where(here, offsets[:, m : m + 1], off)
            ml = jnp.where(here, lens[:, m : m + 1], ml)
            cid = jnp.where(here, c[:, m : m + 1], cid)
            ori = jnp.where(here, orient[:, m : m + 1].astype(jnp.int32), ori)
            wl = jnp.where(here, walk_len[:, m : m + 1], wl)
        q = pos - off
        # rc: base q = complement(base[len-1-q])
        src = jnp.where(ori == 0, q, ml - 1 - q)
        b = contigs.bases[cid, jnp.clip(src, 0, Lc - 1)]
        b = jnp.where(ori == 0, b, kmer.complement_base(b))
        # walked closure bases of the gap after member j
        wq = q - ml
        wb = walk_bases[jnp.arange(B)[:, None], jnp.clip(j, 0),
                        jnp.clip(wq, 0, W - 1)]
        out = jnp.where((j >= 0) & (q < ml), b,
                        jnp.where((j >= 0) & (wq >= 0) & (wq < wl), wb, 4))
        return out.astype(jnp.uint8)

    B = math.gcd(S, RENDER_ROWS)
    blocks = tuple(
        x.reshape((S // B, B) + x.shape[1:])
        for x in (valid, offsets, lens, c, orient, walk_len,
                  walk_bases.reshape(S, M, W))
    )
    out = jax.lax.map(rows, blocks).reshape(S, max_scaffold_len)
    lengths = jnp.minimum(total, max_scaffold_len)
    lengths = jnp.where(n_members > 0, lengths, 0)
    return out, lengths
