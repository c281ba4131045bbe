"""merAligner (paper §II-F, [20]): distributed seed-and-extend alignment.

Seeds (k-mers of the reads) are looked up in a *seed index* — a hash table
over the contig k-mers (UC3 Global Read-Only phase).  Each read votes among
its seeds' candidate placements, keeps the best two distinct-contig
candidates (the second hit is what scaffolding's splint detection consumes),
and verifies each candidate by extension.

Extension scoring here is vectorized Hamming extension (the read model of
the pipeline is substitution-only Illumina, matching the paper's data); the
banded Smith-Waterman Pallas kernel (kernels/sw_extend.py) provides the
gapped path and is validated against the same interface.

TPU adaptation notes: merAligner's software cache for remote seed buckets
(UC3) is replaced by read localization (§II-I / localization.py) which
makes seed traffic owner-local by construction; the voting step replaces
merAligner's per-seed chaining loop with an O(S^2) agreement count over the
static seed positions of each read (S is small).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops

from . import dht, kmer, kmer_analysis
from .types import ContigSet, ReadSet

NONE = jnp.int32(-1)


class SeedIndex(NamedTuple):
    table: dht.HashTable
    contig: jnp.ndarray  # [cap] int32 contig of the (unique) seed
    pos: jnp.ndarray     # [cap] int32 position of seed start on the contig
    flip: jnp.ndarray    # [cap] bool: stored canonical form is the RC of the
    #                       contig's forward-strand k-mer
    multi: jnp.ndarray   # [cap] bool seed occurs at >1 contig position
    seed_len: int


class Alignments(NamedTuple):
    """Top-2 distinct-contig placements per read.

    cstart is the contig coordinate where read base 0 lands (may be
    negative / past the end for overhanging reads).  orient=1 means the
    read aligns as its reverse complement.
    """

    contig: jnp.ndarray   # [R, 2] int32 (-1 absent)
    cstart: jnp.ndarray   # [R, 2] int32
    orient: jnp.ndarray   # [R, 2] uint8
    matches: jnp.ndarray  # [R, 2] int32
    overlap: jnp.ndarray  # [R, 2] int32


def build_seed_index(
    contigs: ContigSet, alive, *, seed_len: int, capacity: int,
    backend=None,
) -> SeedIndex:
    """Index every unique contig k-mer; multi-occurrence seeds are flagged.

    Seed extraction runs through the fused kernel path (`kernels.ops`,
    DESIGN.md §8): the canonical codes and the strand flip come straight
    out of the extraction lanes.
    """
    table, contig_a, pos_a, flip_a, multi_a = _seed_index(
        contigs, alive, seed_len=seed_len, capacity=capacity,
        backend=ops.resolve_backend(backend),
    )
    return SeedIndex(
        table=table, contig=contig_a, pos=pos_a, flip=flip_a, multi=multi_a,
        seed_len=seed_len,
    )


@functools.partial(jax.jit,
                   static_argnames=("seed_len", "capacity", "backend"))
def _seed_index(contigs: ContigSet, alive, *, seed_len: int, capacity: int,
                backend: str):
    """The arrays of `build_seed_index`, as one compiled program (the
    backend is resolved before the call, so an override is never hidden
    by a cached program)."""
    C, W = contigs.bases.shape
    lengths = jnp.where(alive, contigs.lengths, 0)
    # full-width lanes (the last seed_len - 1 windows invalid): one shape,
    # and one compiled build, for every seed length
    lanes = ops.kmer_extract(contigs.bases, lengths, k=seed_len,
                             backend=backend)
    cids = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32)[:, None], (C, W))
    poss = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32)[None, :], (C, W))
    flat = lambda x: x.reshape((-1,))
    # the table holds at most `capacity` seeds: build over the valid ones
    # only, not every pad position of the contig rows
    (fhi, flo, fcid, fpos, fflip), fvalid, _ = kmer_analysis.compact_lanes(
        flat(lanes.valid), capacity,
        flat(lanes.hi), flat(lanes.lo), flat(cids), flat(poss),
        flat(lanes.flip),
    )
    # every occurrence goes in; the table dedupes repeats onto one slot
    table, slots = dht.build(fhi, flo, fvalid, capacity=capacity,
                             backend=backend)
    cap = table.capacity
    sel = jnp.where(fvalid & (slots >= 0), slots, cap)
    # a seed at more than one contig position is multi and never votes
    # (seed_probe), so which occurrence the side arrays keep for it does
    # not matter; a unique seed's single occurrence is the only writer
    occ = jnp.zeros((cap,), jnp.int32).at[sel].add(1, mode="drop")
    contig_a = jnp.full((cap,), NONE).at[sel].set(fcid, mode="drop")
    pos_a = jnp.full((cap,), NONE).at[sel].set(fpos, mode="drop")
    flip_a = jnp.zeros((cap,), bool).at[sel].set(fflip, mode="drop")
    return table, contig_a, pos_a, flip_a, occ > 1


def _seed_positions(read_len_max: int, seed_len: int, stride: int):
    pos = list(range(0, read_len_max - seed_len + 1, stride))
    last = read_len_max - seed_len
    if pos[-1] != last:
        pos.append(last)
    return pos


def _verify(reads: ReadSet, contigs: ContigSet, cid, cstart, orient):
    """Hamming-extension verification of one candidate per read.

    Read columns are padded to the TPU's lane tiling (`kmer.LANE_TILE`):
    a contig gather indexed by an [R, 150] array compiles for minutes on
    a v5e, by an [R, 256] one in seconds.  Pad columns lie past every
    read's length and never count."""
    R, L = reads.bases.shape
    pad = (-L) % kmer.LANE_TILE
    L += pad
    i = jnp.arange(L, dtype=jnp.int32)[None, :]
    fwd_cpos = cstart[:, None] + i
    rc_cpos = cstart[:, None] + (reads.lengths[:, None] - 1 - i)
    cpos = jnp.where(orient[:, None] == 0, fwd_cpos, rc_cpos)
    clen = jnp.where(cid >= 0, contigs.lengths[jnp.clip(cid, 0)], 0)
    inside = (cpos >= 0) & (cpos < clen[:, None]) & (i < reads.lengths[:, None])
    cbase = contigs.bases[jnp.clip(cid, 0)[:, None], jnp.clip(cpos, 0)]
    rbase = jnp.pad(reads.bases, ((0, 0), (0, pad)), constant_values=4)
    rbase_cmp = jnp.where(orient[:, None] == 0, rbase, kmer.complement_base(rbase))
    match = inside & (cbase == rbase_cmp) & (rbase < 4)
    return match.sum(axis=-1), inside.sum(axis=-1)


def _verify_gapped(reads: ReadSet, contigs: ContigSet, cid, cstart, orient,
                   *, backend=None):
    """Banded Smith-Waterman verification via `ops.sw_extend` (gapped path).

    The query is the read oriented onto the contig's forward strand; the
    target is the L-wide contig window starting at cstart (sentinel 4s
    outside the contig, so overhangs score as mismatches exactly like the
    Hamming path treats them as non-matches).  Returns (score, overlap):
    the extension DP score replaces the Hamming match count, and the
    overlap lane keeps the Hamming inside-count so downstream consumers
    (scaffolding's overlap arithmetic, the `ov >= seed_len` floor) see the
    same geometry either way.
    """
    R, L = reads.bases.shape
    _, ov = _verify(reads, contigs, cid, cstart, orient)
    i = jnp.arange(L, dtype=jnp.int32)[None, :]
    rlen = reads.lengths[:, None]
    # reverse-complemented read, front-packed to its live length
    rc_idx = jnp.clip(rlen - 1 - i, 0)
    rc = kmer.complement_base(
        jnp.take_along_axis(reads.bases, rc_idx, axis=1)
    )
    rc = jnp.where(i < rlen, rc, jnp.uint8(4))
    q = jnp.where(orient[:, None] == 0, reads.bases, rc)
    cpos = cstart[:, None] + i
    clen = jnp.where(cid >= 0, contigs.lengths[jnp.clip(cid, 0)], 0)
    tin = (cpos >= 0) & (cpos < clen[:, None])
    t = jnp.where(
        tin, contigs.bases[jnp.clip(cid, 0)[:, None], jnp.clip(cpos, 0)],
        jnp.uint8(4),
    )
    qlen = jnp.where(cid >= 0, reads.lengths, 0)
    tlen = jnp.where(cid >= 0, jnp.int32(L), 0)
    score, _, _ = ops.sw_extend(q, t, qlen, tlen, backend=backend)
    return score, ov


@functools.partial(
    jax.jit, static_argnames=("seed_len", "stride", "min_frac", "gapped",
                              "backend")
)
def align_reads(
    reads: ReadSet,
    contigs: ContigSet,
    index: SeedIndex,
    *,
    seed_len: int,
    stride: int = 16,
    min_frac: float = 0.9,
    gapped: bool = False,
    backend=None,
) -> Alignments:
    """Seed-and-extend alignment of a read batch against the seed index.

    The front half (seed extraction at the static stride positions, seed
    index probe, candidate vote, top-2 distinct-contig selection) is one
    fused `ops.seed_probe` dispatch (DESIGN.md §8).  Verification is
    vectorized Hamming extension by default; `gapped=True` scores through
    the banded Smith-Waterman dispatch (`ops.sw_extend`) instead, with the
    acceptance floor rescaled to the DP's match/mismatch units
    (score >= (2*min_frac - 1) * overlap, equal when gap-free).
    """
    pos_list = _seed_positions(reads.max_len, seed_len, stride)
    t = index.table
    cc, cs, co = ops.seed_probe(
        reads.bases, reads.lengths,
        t.slot_hi, t.slot_lo, t.used, t.max_probe,
        index.contig, index.pos, index.flip, index.multi,
        seed_len=seed_len, positions=tuple(pos_list), backend=backend,
    )
    c1, s1, o1 = cc[:, 0], cs[:, 0], co[:, 0]
    c2, s2, o2 = cc[:, 1], cs[:, 1], co[:, 1]
    if gapped:
        m1, ov1 = _verify_gapped(reads, contigs, c1, s1, o1, backend=backend)
        m2, ov2 = _verify_gapped(reads, contigs, c2, s2, o2, backend=backend)
        floor = 2.0 * min_frac - 1.0
        ok1 = (c1 >= 0) & (m1 >= floor * jnp.maximum(ov1, 1)) & (ov1 >= index.seed_len)
        ok2 = (c2 >= 0) & (m2 >= floor * jnp.maximum(ov2, 1)) & (ov2 >= index.seed_len)
        return Alignments(
            contig=jnp.stack(
                [jnp.where(ok1, c1, NONE), jnp.where(ok2, c2, NONE)], axis=1
            ),
            cstart=jnp.stack([s1, s2], axis=1),
            orient=jnp.stack([o1, o2], axis=1),
            matches=jnp.stack([m1, m2], axis=1),
            overlap=jnp.stack([ov1, ov2], axis=1),
        )
    m1, ov1 = _verify(reads, contigs, c1, s1, o1)
    m2, ov2 = _verify(reads, contigs, c2, s2, o2)
    ok1 = (c1 >= 0) & (m1 >= min_frac * jnp.maximum(ov1, 1)) & (ov1 >= index.seed_len)
    ok2 = (c2 >= 0) & (m2 >= min_frac * jnp.maximum(ov2, 1)) & (ov2 >= index.seed_len)
    return Alignments(
        contig=jnp.stack([jnp.where(ok1, c1, NONE), jnp.where(ok2, c2, NONE)], axis=1),
        cstart=jnp.stack([s1, s2], axis=1),
        orient=jnp.stack([o1, o2], axis=1),
        matches=jnp.stack([m1, m2], axis=1),
        overlap=jnp.stack([ov1, ov2], axis=1),
    )
