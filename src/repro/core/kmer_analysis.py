"""K-mer analysis (paper §II-B): distributed histogram with extensions.

UPC MetaHipMer routes raw k-mer occurrences to owner processors (UC1
aggregated all-to-all) and counts them in local hash tables (UC4).  The
TPU-idiomatic equivalent of a local counting hash table is radix sort +
run-length segmentation: sort the packed canonical codes, find group
boundaries, and segment-sum occurrence / extension histograms.  The sort
IS the hash table — same asymptotic work, fully vectorized, and the
receiving shard's "cache reuse after read localization" (§II-I) becomes a
literal reduction in sort entropy.

The MetaHipMer contribution lives in `compute_extensions`: the adaptive
high-quality-extension threshold t_hq = max(t_base, e * depth) (§II-C)
replaces HipMer's global constant, so high-coverage genomes tolerate
proportionally more contradicting extensions.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops

from . import bloom, kmer
from .types import EMPTY_HI, EXT_F, EXT_X, INVALID_BASE, KmerSet, ReadSet


class ExtensionPolicy(NamedTuple):
    """MetaHipMer §II-C extension rule.

    A side's extension is the most common base iff
      (a) its count >= min_ext          (quality floor, HipMer t_hq role)
      (b) contradicting occurrences <= max(t_base, err_rate * depth)
    err_rate=0.0 recovers the HipMer fixed-threshold baseline.
    """

    min_ext: int = 2
    t_base: float = 2.0
    err_rate: float = 0.05


def occurrences(reads: ReadSet, *, k: int, backend=None):
    """Flat canonical k-mer occurrences of a read batch.

    One fused `kernels.ops.kmer_extract` invocation per read tile produces
    the canonical codes, the canonicalized extension bases, and the
    validity mask together (DESIGN.md §8) — this is the system's ONE
    extraction path; `backend` selects pallas/ref per the plan or the
    REPRO_KERNELS override.

    Returns (hi, lo, left, right, valid), each [R * Lp]: the lanes keep the
    read width, the last k - 1 windows of a row invalid, so every k of the
    ladder hands the count fold one shape and it compiles once.  Rows are
    padded to Lp, the width rounded up to the TPU's lane tiling
    (`kmer.flat_lanes`), with invalid lanes.
    """
    return _occurrences(reads.bases, reads.lengths, k=k,
                        backend=ops.resolve_backend(backend))


@functools.partial(jax.jit, static_argnames=("k", "backend"))
def _occurrences(bases, lengths, *, k: int, backend: str):
    lanes = ops.kmer_extract(bases, lengths, k=k, backend=backend)
    flat = kmer.flat_lanes
    return (flat(lanes.hi, 0), flat(lanes.lo, 0),
            flat(lanes.left, INVALID_BASE), flat(lanes.right, INVALID_BASE),
            flat(lanes.valid, False))


def compact_lanes(valid, size: int, *lanes):
    """The valid entries of flat lanes, in order, packed into `size` slots.

    Contig-scale rows are padded to contig_cap x max_contig_len (~10^8
    positions at pipeline widths) while they hold ~10^6 k-mers; a sort over
    the padded lanes costs minutes of TPU compile and a pass over every
    pad.  Returns (lanes, valid, overflow): `lanes` keep the first
    min(n, size) valid entries in their original order (the rest of each
    lane is filler, `valid` False), and `overflow` is n > size.  Lanes
    no longer than `size` pass through unchanged.
    """
    if valid.shape[0] <= size:
        return lanes, valid, jnp.asarray(False)
    n = valid.sum(dtype=jnp.int32)
    idx = jnp.nonzero(valid, size=size, fill_value=0)[0]
    return (tuple(x[idx] for x in lanes), jnp.arange(size) < n, n > size)


def pseudo_count_table(bases, lengths, *, k: int, capacity: int,
                       weight: int, backend=None) -> dict:
    """Pseudo-counted k-mer table from dense sequence rows (§II-H).

    The cross-iteration evidence carrier: contig (k+s)-mers enter the next
    round's count table weighted by `weight`, so they survive the
    count/extension thresholds where read support is thin.  Shared by the
    Local merge path and the Mesh owner exchange — the S=1 oracle test
    relies on both using exactly this weighting.
    """
    return _pseudo_count_table(bases, lengths, k=k, capacity=capacity,
                               weight=weight,
                               backend=ops.resolve_backend(backend))


@functools.partial(jax.jit,
                   static_argnames=("k", "capacity", "weight", "backend"))
def _pseudo_count_table(bases, lengths, *, k: int, capacity: int,
                        weight: int, backend: str) -> dict:
    seqs = ReadSet(
        bases=bases, lengths=lengths,
        mate=jnp.full(lengths.shape, -1, jnp.int32), insert_size=0,
    )
    hi, lo, left, right, valid = occurrences(seqs, k=k, backend=backend)
    # a table of `capacity` keys holds no more distinct k-mers than that,
    # and a contig set repeats few, so `capacity` occurrences bound the fold
    (hi, lo, left, right), valid, cut = compact_lanes(
        valid, capacity, hi, lo, left, right
    )
    tab = count_occurrences(hi, lo, left, right, valid, capacity=capacity)
    w = jnp.int32(weight)
    return {
        **tab,
        "overflow": tab["overflow"] | cut,
        "count": tab["count"] * w,
        "left_cnt": tab["left_cnt"] * w,
        "right_cnt": tab["right_cnt"] * w,
    }


def merge_contig_kmers(tab: dict, bases, lengths, *, k: int, capacity: int,
                       weight: int, backend=None) -> dict:
    """`merge_counts(tab, pseudo_count_table(bases, lengths, ...))` in one
    fold (§II-H).

    The contig occurrences join the merge as rows of weight `weight`
    instead of through a count table of their own: the sums per key are
    the same, with one sort fewer to compile and run.  `overflow` also
    flags contig occurrences cut at `capacity`.
    """
    return _merge_contig_kmers(tab, bases, lengths, k=k, capacity=capacity,
                               weight=weight,
                               backend=ops.resolve_backend(backend))


@functools.partial(jax.jit,
                   static_argnames=("k", "capacity", "weight", "backend"))
def _merge_contig_kmers(tab, bases, lengths, *, k: int, capacity: int,
                        weight: int, backend: str) -> dict:
    seqs = ReadSet(
        bases=bases, lengths=lengths,
        mate=jnp.full(lengths.shape, -1, jnp.int32), insert_size=0,
    )
    hi, lo, left, right, valid = occurrences(seqs, k=k, backend=backend)
    (hi, lo, left, right), valid, cut = compact_lanes(
        valid, capacity, hi, lo, left, right
    )
    valid = valid & (weight > 0)
    w = jnp.where(valid, jnp.int32(weight), 0)
    bases4 = jnp.arange(4, dtype=jnp.uint8)[None, :]
    hist = lambda ext: jnp.where(ext[:, None] == bases4, w[:, None], 0)
    cat = lambda a, b: jnp.concatenate([a, b])
    out = aggregate_weighted(
        cat(tab["hi"], hi), cat(tab["lo"], lo), cat(tab["count"], w),
        cat(tab["left_cnt"], hist(left)), cat(tab["right_cnt"], hist(right)),
        cat(tab["count"] > 0, valid), capacity=capacity,
    )
    return {**out, "overflow": out["overflow"] | cut}


def _group_segments(shi, slo, sv):
    """Boundary flags + segment ids of equal-key runs in sorted order."""
    prev_ne = jnp.concatenate(
        [jnp.ones((1,), bool), (shi[1:] != shi[:-1]) | (slo[1:] != slo[:-1])]
    )
    new_grp = sv & prev_ne
    seg = jnp.cumsum(new_grp.astype(jnp.int32)) - 1
    n_unique = jnp.where(jnp.any(sv), seg[-1] + jnp.any(sv).astype(jnp.int32), 0)
    return new_grp, seg, n_unique


@functools.partial(jax.jit, static_argnames=("capacity",))
def count_occurrences(hi, lo, left, right, valid, *, capacity: int):
    """Sort-based exact counting of canonical k-mer occurrences.

    Returns a dict of dense arrays of length `capacity`; live entries are
    packed at the front in sorted key order.  `n_unique` may exceed
    `capacity` — callers must check `overflow`.
    """
    # push invalids to the end of the sort order; a valid key never equals
    # EMPTY_HI, so validity comes back from the sorted key.  The extension
    # bases ride as one packed payload, and the sort need not be stable:
    # counts and histograms do not depend on the order within a key, and
    # XLA:TPU compiles a sort much faster with fewer operands.
    shi = jnp.where(valid, hi, EMPTY_HI)
    slo = jnp.where(valid, lo, jnp.uint32(0xFFFFFFFF))
    ext = (left.astype(jnp.int32) << 8) | right.astype(jnp.int32)
    shi, slo, ext = jax.lax.sort((shi, slo, ext), num_keys=2, is_stable=False)
    sl, sr = ext >> 8, ext & 0xFF
    sv = shi != EMPTY_HI
    new_grp, seg, n_unique = _group_segments(shi, slo, sv)
    # invalid rows scatter out of bounds (dropped)
    seg_d = jnp.where(sv, seg, capacity)
    counts = jnp.zeros((capacity,), jnp.int32).at[seg_d].add(1, mode="drop")
    # extension histograms; ext >= 4 (absent) dropped
    lseg = jnp.where(sv & (sl < 4), seg, capacity)
    rseg = jnp.where(sv & (sr < 4), seg, capacity)
    lcnt = jnp.zeros((capacity, 4), jnp.int32).at[lseg, sl & 3].add(
        1, mode="drop"
    )
    rcnt = jnp.zeros((capacity, 4), jnp.int32).at[rseg, sr & 3].add(
        1, mode="drop"
    )
    out_hi = jnp.full((capacity,), EMPTY_HI, jnp.uint32)
    out_lo = jnp.zeros((capacity,), jnp.uint32)
    bseg = jnp.where(new_grp, seg, capacity)
    out_hi = out_hi.at[bseg].set(shi, mode="drop")
    out_lo = out_lo.at[bseg].set(slo, mode="drop")
    return {
        "hi": out_hi,
        "lo": out_lo,
        "count": counts,
        "left_cnt": lcnt,
        "right_cnt": rcnt,
        "n_unique": n_unique,
        "overflow": n_unique > capacity,
    }


@functools.partial(jax.jit, static_argnames=("capacity",))
def aggregate_weighted(hi, lo, cnt, lcnt, rcnt, valid, *, capacity: int) -> dict:
    """Sum weighted partial counts per key (sort + segment reduce).

    The receiver half of the UC4 pattern: after the owner exchange, each
    shard holds (key, partial count, partial histograms) tuples from every
    sender and reduces them associatively.  Also the backbone of the
    heavy-hitter pre-combining (§II-B) and of cross-iteration merging.
    """
    shi = jnp.where(valid, hi, EMPTY_HI)
    slo = jnp.where(valid, lo, jnp.uint32(0xFFFFFFFF))
    idx = jnp.arange(hi.shape[0], dtype=jnp.int32)
    # as in count_occurrences: sums are order-free within a key
    shi, slo, perm = jax.lax.sort((shi, slo, idx), num_keys=2, is_stable=False)
    sv = shi != EMPTY_HI
    cnt, lcnt, rcnt = cnt[perm], lcnt[perm], rcnt[perm]
    new_grp, seg, n_unique = _group_segments(shi, slo, sv)
    seg_d = jnp.where(sv, seg, capacity)
    counts = jnp.zeros((capacity,), jnp.int32).at[seg_d].add(cnt, mode="drop")
    lout = jnp.zeros((capacity, 4), jnp.int32).at[seg_d].add(lcnt, mode="drop")
    rout = jnp.zeros((capacity, 4), jnp.int32).at[seg_d].add(rcnt, mode="drop")
    out_hi = jnp.full((capacity,), EMPTY_HI, jnp.uint32)
    out_lo = jnp.zeros((capacity,), jnp.uint32)
    bseg = jnp.where(new_grp, seg, capacity)
    out_hi = out_hi.at[bseg].set(shi, mode="drop")
    out_lo = out_lo.at[bseg].set(slo, mode="drop")
    return {
        "hi": out_hi,
        "lo": out_lo,
        "count": counts,
        "left_cnt": lout,
        "right_cnt": rout,
        "n_unique": n_unique,
        "overflow": n_unique > capacity,
    }


@functools.partial(jax.jit, static_argnames=("capacity",))
def merge_counts(a: dict, b: dict, *, capacity: int) -> dict:
    """Union two count tables (same k), summing histograms (§II-H)."""
    return aggregate_weighted(
        jnp.concatenate([a["hi"], b["hi"]]),
        jnp.concatenate([a["lo"], b["lo"]]),
        jnp.concatenate([a["count"], b["count"]]),
        jnp.concatenate([a["left_cnt"], b["left_cnt"]]),
        jnp.concatenate([a["right_cnt"], b["right_cnt"]]),
        jnp.concatenate([a["count"] > 0, b["count"] > 0]),
        capacity=capacity,
    )


def compute_extensions(count, left_cnt, right_cnt, policy: ExtensionPolicy):
    """EXT_* code per side under the MetaHipMer adaptive threshold."""
    depth = count.astype(jnp.float32)
    t_hq = jnp.maximum(policy.t_base, policy.err_rate * depth)

    def side(cnt):
        total = cnt.sum(axis=-1)
        c1 = cnt.max(axis=-1)
        b1 = cnt.argmax(axis=-1).astype(jnp.uint8)
        contradict = (total - c1).astype(jnp.float32)
        ok = (c1 >= policy.min_ext) & (contradict <= t_hq)
        return jnp.where(total == 0, EXT_X, jnp.where(ok, b1, EXT_F)).astype(jnp.uint8)

    return side(left_cnt), side(right_cnt)


def dup_in_chunk(hi, lo, valid):
    """Flag the 2nd+ occurrence of each key within the chunk (exact, sorted)."""
    shi = jnp.where(valid, hi, EMPTY_HI)
    slo = jnp.where(valid, lo, jnp.uint32(0xFFFFFFFF))
    idx = jnp.arange(hi.shape[0], dtype=jnp.int32)
    o_hi, o_lo, o_idx = jax.lax.sort((shi, slo, idx), num_keys=2)
    dup_sorted = jnp.concatenate(
        [
            jnp.zeros((1,), bool),
            (o_hi[1:] == o_hi[:-1]) & (o_lo[1:] == o_lo[:-1]) & (o_hi[1:] != EMPTY_HI),
        ]
    )
    return jnp.zeros(hi.shape, bool).at[o_idx].set(dup_sorted)


_dup_in_chunk = dup_in_chunk  # historical private name


def bloom_observe(f1: "bloom.BloomFilter", f2: "bloom.BloomFilter",
                  hi, lo, valid):
    """One pass-1 step of the two-sighting rule over one occurrence batch.

    Keys already in f1 (sighted in an EARLIER batch) or duplicated within
    this batch (exact, via sort) are marked "seen twice" in f2; every key
    then enters f1.  Querying f1 against the state *prior* to this batch
    preserves the no-false-negative guarantee.  This is the persistent-
    state building block shared by the in-memory chunked admission below
    and the out-of-core streaming ingest (repro.stream, DESIGN.md §7).
    """
    seen = bloom.query(f1, hi, lo) | dup_in_chunk(hi, lo, valid)
    f2 = bloom.insert(f2, hi, lo, valid & seen)
    f1 = bloom.insert(f1, hi, lo, valid)
    return f1, f2


def bloom_admit(f2: "bloom.BloomFilter", hi, lo, valid):
    """Pass-2 admission: keep occurrences whose key was sighted >= twice.

    No false negatives; Bloom false positives let a few singletons
    through, which the exact min_count filter downstream removes.
    """
    return valid & bloom.query(f2, hi, lo)


def empty_count_table(capacity: int) -> dict:
    """An empty count table, the identity element of `merge_counts`.

    Seeds the running owner-partitioned fold of the streaming ingest:
    `run = merge_counts(run, batch_table)` folds per-batch partials into a
    persistent table of fixed `capacity` (DESIGN.md §7).
    """
    return {
        "hi": jnp.full((capacity,), EMPTY_HI, jnp.uint32),
        "lo": jnp.zeros((capacity,), jnp.uint32),
        "count": jnp.zeros((capacity,), jnp.int32),
        "left_cnt": jnp.zeros((capacity, 4), jnp.int32),
        "right_cnt": jnp.zeros((capacity, 4), jnp.int32),
        "n_unique": jnp.int32(0),
        "overflow": jnp.asarray(False),
    }


def admit_two_sightings(hi, lo, valid, *, bloom_bits: int, num_chunks: int = 4):
    """Paper's Bloom-filter two-pass admission (§II-B, HipMer [14]).

    Pass 1 streams occurrence chunks through Bloom filter f1; an occurrence
    whose key was already in f1 (or duplicated earlier in its own chunk)
    marks the key as "seen twice" in a second filter f2.  Pass 2 admits
    occurrences whose key is in f2.  No false negatives (every true >=2
    k-mer is admitted); false positives let a few singletons through, which
    the exact min_count filter downstream removes.
    """
    n = hi.shape[0]
    chunk = -(-n // num_chunks)
    f1 = bloom.empty(bloom_bits)
    f2 = bloom.empty(bloom_bits)
    for c in range(num_chunks):
        sl = slice(c * chunk, min((c + 1) * chunk, n))
        if sl.start >= n:
            break
        f1, f2 = bloom_observe(f1, f2, hi[sl], lo[sl], valid[sl])
    return bloom_admit(f2, hi, lo, valid)


def analyze(
    reads: ReadSet,
    *,
    k: int,
    capacity: int,
    min_count: int = 2,
    policy: ExtensionPolicy = ExtensionPolicy(),
    low_memory: bool = False,
    bloom_bits: int = 1 << 16,
    backend=None,
) -> KmerSet:
    """Full single-shard k-mer analysis: occurrences -> counted KmerSet.

    `low_memory=True` reproduces the paper's Bloom-filter pre-pass: only
    k-mers sighted at least twice are admitted to counting, so `capacity`
    can be provisioned for the true (multi-occurrence) k-mer population
    rather than the error-singleton-dominated raw population.
    """
    hi, lo, left, right, valid = occurrences(reads, k=k, backend=backend)
    if low_memory:
        valid = admit_two_sightings(hi, lo, valid, bloom_bits=bloom_bits)
    tab = count_occurrences(hi, lo, left, right, valid, capacity=capacity)
    return finalize(tab, min_count=min_count, policy=policy)


@functools.partial(jax.jit, static_argnames=("min_count", "policy"))
def finalize(tab: dict, *, min_count: int, policy: ExtensionPolicy) -> KmerSet:
    """Apply the count floor and extension policy to a raw count table."""
    used = tab["count"] >= min_count
    lext, rext = compute_extensions(tab["count"], tab["left_cnt"], tab["right_cnt"], policy)
    return KmerSet(
        hi=tab["hi"],
        lo=tab["lo"],
        count=jnp.where(used, tab["count"], 0),
        left_cnt=tab["left_cnt"],
        right_cnt=tab["right_cnt"],
        left_ext=jnp.where(used, lext, jnp.uint8(EXT_X)),
        right_ext=jnp.where(used, rext, jnp.uint8(EXT_X)),
        used=used,
    )
