"""Local assembly by mer-walking (paper §II-G).

Contigs are extended past their ends using only the reads localized to each
contig (aligned there, or mates projected into the flanking gap).  Because
the mer tables are keyed by (contig, mer), erroneous k-mers from
high-coverage regions cannot contaminate low-depth loci — the paper's core
argument for recovering k-mers that global analysis rejected.

Mechanics preserved from the paper:
  * dynamic mer-size ladder: upshift (+L) on fork, downshift (-L) on dead
    end; terminate on fork-after-downshift / deadend-after-upshift;
  * uncontested low-quality extensions are accepted (min_votes=1), unlike
    the global extension policy.

TPU adaptation: UPC work stealing balanced unpredictable per-walk costs
across processors; here every walker advances in vectorized lockstep (one
fused step loop over all 2C contig ends), so imbalance dissolves into SIMD
lane predication — the BSP analogue of stealing (DESIGN.md §2).  The
(contig, mer) key is the mer code with the contig id embedded in the spare
high bits of the dual-lane key (kmer.embed_tag), turning per-contig
isolation into plain hash-table keying.  The walk itself is a fused
kernel hot path: `mer_walk` dispatches through `kernels.ops.mer_walk`
(Pallas kernel or bit-identical jnp ref, DESIGN.md §8) so the per-step
suffix update, three-rung tagged probe, ladder vote, and base append run
in one pass per walker tile.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops

from . import dht, kmer
from .types import INVALID_BASE, ContigSet, ReadSet

NONE = jnp.int32(-1)

# single source of truth for the walk's buffer width and status codes is
# the fused kernel (HIT: gap walk reached its target seed, §III-D)
from repro.kernels.mer_walk import (  # noqa: E402
    ACTIVE, BUF_K, DEADEND, DONE, FORK, HIT,
)


class WalkTables(NamedTuple):
    """One tagged-mer hash table per ladder rung.

    NOTE: mer_sizes is deliberately NOT stored here — it must stay a static
    (Python) value for the jitted walk, so it is threaded separately.
    """

    tables: tuple            # tuple[dht.HashTable]
    right_hist: tuple        # tuple[[cap, 4] int32]
    left_hist: tuple


@jax.jit
def localize_reads(reads: ReadSet, aln_contig):
    """Read -> contig assignment: own alignment, else the mate's (§II-G)."""
    own = aln_contig
    mate = jnp.where(reads.mate >= 0, aln_contig[jnp.clip(reads.mate, 0)], NONE)
    return jnp.where(own >= 0, own, mate)


def _count_tagged(chi, clo, cleft, cright, valid, tag, *, m: int,
                  tag_bits: int, table: dht.HashTable, lh, rh,
                  backend=None):
    """Tag and histogram canonical (contig,mer) occurrences into a DHT.

    Inputs are the already-canonical lanes from the fused extraction kernel
    (`kernels.ops.kmer_extract`, DESIGN.md §8).  Inserts into the given
    table through the dispatched `dht.insert` (the `ops.dht_insert` hot
    path) and accumulates onto the given histograms, so repeated calls fold
    successive occurrence batches into one persistent table (the streaming
    ingest path, DESIGN.md §7).  `dht.insert` dedupes against existing
    entries, and histogram updates are scatter-adds at the returned slots,
    so the result is batch-split independent.
    """
    thi, tlo = kmer.embed_tag(chi, clo, tag, k=m, tag_bits=tag_bits)
    table, slots = dht.insert(table, thi, tlo, valid, backend=backend)
    cap = table.capacity
    lsel = jnp.where(valid & (slots >= 0) & (cleft < 4), slots, cap)
    rsel = jnp.where(valid & (slots >= 0) & (cright < 4), slots, cap)
    lh = lh.at[lsel, cleft.astype(jnp.int32) & 3].add(1, mode="drop")
    rh = rh.at[rsel, cright.astype(jnp.int32) & 3].add(1, mode="drop")
    return table, lh, rh


def empty_walk_tables(*, mer_sizes: tuple, capacity: int) -> WalkTables:
    """Empty per-rung tables, the identity of `accumulate_walk_tables`."""
    n = len(mer_sizes)
    return WalkTables(
        tables=tuple(dht.empty_table(capacity) for _ in range(n)),
        right_hist=tuple(jnp.zeros((capacity, 4), jnp.int32) for _ in range(n)),
        left_hist=tuple(jnp.zeros((capacity, 4), jnp.int32) for _ in range(n)),
    )


def accumulate_walk_tables(
    wt: WalkTables,
    reads: ReadSet,
    read_contig,
    *,
    mer_sizes: tuple,
    tag_bits: int,
    backend=None,
) -> WalkTables:
    """Fold one read batch's (contig, mer) occurrences into `wt`.

    The out-of-core half of `build_walk_tables`: batches stream through
    here one at a time, so the device never holds more than one batch of
    read state while the (fixed-capacity) tables accumulate the evidence
    of the whole dataset.  Per-rung extraction runs through the fused
    kernel path (`kernels.ops`), which emits the canonical codes and
    canonicalized extensions in one pass.
    """
    return _accumulate_walk_tables(
        wt, reads, read_contig, mer_sizes=tuple(mer_sizes), tag_bits=tag_bits,
        backend=ops.resolve_backend(backend),
    )


@functools.partial(jax.jit,
                   static_argnames=("mer_sizes", "tag_bits", "backend"))
def _accumulate_walk_tables(wt, reads, read_contig, *, mer_sizes: tuple,
                            tag_bits: int, backend: str) -> WalkTables:
    tables, lhs, rhs = [], [], []
    # every rung inserts [R * Lp] lanes (the last m - 1 of a row and the
    # tiling pad invalid, kmer.flat_lanes), so the rungs' inserts share a
    # shape
    tag = kmer.flat_lanes(
        jnp.broadcast_to(read_contig[:, None], reads.bases.shape), 0)
    for rung, m in enumerate(mer_sizes):
        lanes = ops.kmer_extract(reads.bases, reads.lengths, k=m,
                                 backend=backend)
        v = lanes.valid & (read_contig[:, None] >= 0)
        t, lh, rh = _count_tagged(
            kmer.flat_lanes(lanes.hi, 0), kmer.flat_lanes(lanes.lo, 0),
            kmer.flat_lanes(lanes.left, INVALID_BASE),
            kmer.flat_lanes(lanes.right, INVALID_BASE),
            kmer.flat_lanes(v, False), tag, m=m, tag_bits=tag_bits,
            table=wt.tables[rung], lh=wt.left_hist[rung],
            rh=wt.right_hist[rung], backend=backend,
        )
        tables.append(t)
        lhs.append(lh)
        rhs.append(rh)
    return WalkTables(
        tables=tuple(tables), right_hist=tuple(rhs), left_hist=tuple(lhs)
    )


def build_walk_tables(
    reads: ReadSet,
    read_contig,
    *,
    mer_sizes: tuple,
    tag_bits: int,
    capacity: int,
    backend=None,
) -> WalkTables:
    return accumulate_walk_tables(
        empty_walk_tables(mer_sizes=mer_sizes, capacity=capacity),
        reads, read_contig, mer_sizes=mer_sizes, tag_bits=tag_bits,
        backend=backend,
    )


def _suffix_mer(buf_hi, buf_lo, m: int):
    """Last m bases of the BUF_K-wide rolling buffer = low 2m bits."""
    mask_lo, mask_hi = kmer._masks(m)
    return buf_hi & mask_hi, buf_lo & mask_lo


class WalkResult(NamedTuple):
    ext_bases: jnp.ndarray   # [E, max_ext] uint8 accepted bases (4 pad)
    ext_len: jnp.ndarray     # [E] int32
    status: jnp.ndarray      # [E] final status code


def mer_walk(
    wt: WalkTables,
    start_hi,
    start_lo,
    contig,
    active0,
    *,
    mer_sizes: tuple,
    tag_bits: int,
    max_ext: int = 64,
    min_votes: int = 1,
    dominance: int = 4,
    backend=None,
) -> WalkResult:
    """Vectorized dynamic-mer walk for E walkers (2 per contig).

    start_hi/lo: BUF_K-wide packed suffix of each walker's contig end,
    oriented so the walk appends rightward.  The walk itself is the fused
    `ops.mer_walk` hot path (DESIGN.md §8); this wrapper keeps the
    historical WalkResult shape for the extension/graft pipeline.
    """
    out = ops.mer_walk(
        wt, start_hi, start_lo, contig, active0,
        mer_sizes=tuple(mer_sizes), tag_bits=tag_bits, max_ext=max_ext,
        min_votes=min_votes, dominance=dominance, backend=backend,
    )
    return WalkResult(ext_bases=out.ext_bases, ext_len=out.ext_len,
                      status=out.status)


def contig_end_buffers(contigs: ContigSet, alive):
    """BUF_K-wide packed suffix per contig end, oriented to extend rightward.

    End 0 (left): the RC of the contig prefix; end 1 (right): the suffix.
    Short contigs (< BUF_K) pad with leading A's — harmless because suffix
    mers never reach past the real bases for m <= contig length, and walks
    on contigs shorter than the smallest rung are disabled by the caller.
    """
    C, Lmax = contigs.bases.shape
    idx = jnp.arange(BUF_K, dtype=jnp.int32)[None, :]
    L = contigs.lengths[:, None]
    # suffix: last BUF_K bases (clamped)
    suf_pos = jnp.clip(L - BUF_K + idx, 0, Lmax - 1)
    suffix = jnp.take_along_axis(contigs.bases, suf_pos, axis=1)
    suffix = jnp.where(suffix > 3, 0, suffix)  # pad -> A
    s_hi, s_lo = kmer.pack_window(suffix, k=BUF_K)
    # prefix RC'd: first BUF_K bases, reverse-complemented
    pre_pos = jnp.clip(idx, 0, Lmax - 1)
    prefix = jnp.take_along_axis(contigs.bases, pre_pos, axis=1)
    prefix = jnp.where(prefix > 3, 0, prefix)
    p_hi, p_lo = kmer.pack_window(prefix, k=BUF_K)
    rp_hi, rp_lo = kmer.reverse_complement(p_hi, p_lo, k=BUF_K)
    return (
        jnp.concatenate([rp_hi, s_hi]),
        jnp.concatenate([rp_lo, s_lo]),
        jnp.concatenate([alive, alive]),
    )


@functools.partial(jax.jit, static_argnames=())
def apply_extensions(contigs: ContigSet, alive, walk: WalkResult):
    """Graft the walked bases onto the contigs (left end RC'd back)."""
    C, Lmax = contigs.bases.shape
    max_ext = walk.ext_bases.shape[1]
    lext = walk.ext_bases[:C]      # left walks (in RC frame)
    rext = walk.ext_bases[C:]
    nL = jnp.where(alive, walk.ext_len[:C], 0)
    nR = jnp.where(alive, walk.ext_len[C:], 0)
    L = contigs.lengths
    new_len = jnp.minimum(L + nL + nR, Lmax)
    i = jnp.arange(Lmax, dtype=jnp.int32)[None, :]
    # zone 1: prepended bases = complement(lext[nL-1-i])
    lidx = jnp.clip(nL[:, None] - 1 - i, 0, max_ext - 1)
    z1 = kmer.complement_base(jnp.take_along_axis(lext, lidx, axis=1))
    # zone 2: original bases shifted right by nL
    oidx = jnp.clip(i - nL[:, None], 0, Lmax - 1)
    z2 = jnp.take_along_axis(contigs.bases, oidx, axis=1)
    # zone 3: appended bases
    ridx = jnp.clip(i - nL[:, None] - L[:, None], 0, max_ext - 1)
    z3 = jnp.take_along_axis(rext, ridx, axis=1)
    out = jnp.where(
        i < nL[:, None],
        z1,
        jnp.where(i < (nL + L)[:, None], z2, jnp.where(i < new_len[:, None], z3, 4)),
    ).astype(jnp.uint8)
    out = jnp.where(alive[:, None], out, contigs.bases)
    new_len = jnp.where(alive, new_len, contigs.lengths)
    return ContigSet(bases=out, lengths=new_len, depths=contigs.depths)


def extend_with_tables(
    wt: WalkTables,
    contigs: ContigSet,
    alive,
    *,
    mer_sizes: tuple,
    max_ext: int = 64,
    min_len: int | None = None,
    backend=None,
):
    """Walk both ends from prebuilt tables and graft the extensions.

    The contig-scale half of §II-G, shared by the in-memory path (tables
    built in one shot) and the streaming path (tables accumulated batch by
    batch, DESIGN.md §7).
    """
    return _extend_with_tables(
        wt, contigs, alive, mer_sizes=tuple(mer_sizes), max_ext=max_ext,
        min_len=min_len, backend=ops.resolve_backend(backend),
    )


@functools.partial(jax.jit, static_argnames=("mer_sizes", "max_ext",
                                             "min_len", "backend"))
def _extend_with_tables(wt, contigs, alive, *, mer_sizes: tuple, max_ext: int,
                        min_len, backend: str):
    C = contigs.capacity
    tag_bits = min(16, 62 - 2 * max(mer_sizes))
    assert C <= (1 << tag_bits), (
        f"contig capacity {C} exceeds tag space {1 << tag_bits}"
    )
    bhi, blo, act = contig_end_buffers(contigs, alive)
    min_len = min_len if min_len is not None else max(mer_sizes)
    long_enough = contigs.lengths >= min_len
    act = act & jnp.concatenate([long_enough, long_enough])
    walker_contig = jnp.concatenate(
        [jnp.arange(C, dtype=jnp.int32), jnp.arange(C, dtype=jnp.int32)]
    )
    walk = mer_walk(
        wt, bhi, blo, walker_contig, act, mer_sizes=tuple(mer_sizes),
        tag_bits=tag_bits, max_ext=max_ext, backend=backend,
    )
    return apply_extensions(contigs, alive, walk), walk


def extend_contigs(
    reads: ReadSet,
    contigs: ContigSet,
    alive,
    aln_contig,
    *,
    mer_sizes: tuple = (17, 21, 25),
    capacity: int = 1 << 16,
    max_ext: int = 64,
    min_len: int | None = None,
    backend=None,
):
    """Full §II-G stage: localize -> tables -> walk both ends -> graft."""
    C = contigs.capacity
    tag_bits = min(16, 62 - 2 * max(mer_sizes))
    assert C <= (1 << tag_bits), (
        f"contig capacity {C} exceeds tag space {1 << tag_bits}"
    )
    read_contig = localize_reads(reads, aln_contig)
    wt = build_walk_tables(
        reads, read_contig, mer_sizes=mer_sizes, tag_bits=tag_bits,
        capacity=capacity, backend=backend,
    )
    return extend_with_tables(
        wt, contigs, alive, mer_sizes=mer_sizes, max_ext=max_ext,
        min_len=min_len, backend=backend,
    )
