"""MGSim communities and reads (Georganas et al., SC18, section IV-A).

The protocol of the program's `repro.data.mgsim`, kept here so that the
traffic cannot change with the program: genomes of uniform random bases,
log-normal relative abundances, WGSim-style fr read pairs with
substitution errors.  Unlike that copy, fragments are not lengthened to
two reads, so the reads have the insert size the assembler is told.  Vectorised over pairs, so a sample of thousands of
pairs generates in milliseconds.  The same (seed, index) gives the same
sample.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

COMPLEMENT = np.array([3, 2, 1, 0, 4], dtype=np.uint8)


@dataclass
class Sample:
    """One sample: the reads as the assembler gets them, and the truth."""

    bases: np.ndarray       # [R, L] uint8, A=0 C=1 G=2 T=3
    lengths: np.ndarray     # [R] int32
    mate: np.ndarray        # [R] int32, reads 2i and 2i+1 are mates
    insert_size: int
    genomes: list           # [G] uint8 arrays
    coverage: np.ndarray    # [G] expected read coverage of each genome

    @property
    def read_bases(self) -> int:
        return int(self.lengths.sum())


def sample(seed: int, index: int, *, pairs: int, community: dict,
           reads: dict) -> Sample:
    """The `index`-th sample of a run seeded with `seed`.

    The sample's sizes (genome lengths, log-normal abundances, and so the
    pairs drawn from each genome) come from the index alone, from a fixed
    stream (`size_seed`): every seed assembles the same sizes in the same
    order, so runs on different seeds do the same amount of work.  The
    seed draws everything else: the genomes' bases, the fragments, their
    strands and the read errors."""
    sizes = np.random.default_rng(
        np.random.SeedSequence([community.get("size_seed", 0), index]))
    n_genomes = max(1, round(pairs / community["pairs_per_genome"]))
    lo, hi = community["genome_len"]
    lens = sizes.integers(lo, hi, size=n_genomes)
    ab = sizes.lognormal(0.0, community["abundance_sigma"], size=n_genomes)
    per_genome = _apportion(pairs, ab / ab.sum())
    rng = np.random.default_rng(np.random.SeedSequence([seed, index]))
    genomes = [rng.integers(0, 4, size=int(n), dtype=np.uint8) for n in lens]
    gid = rng.permutation(np.repeat(np.arange(n_genomes), per_genome))
    bases, lengths, mate = _pairs(rng, genomes, gid, **reads)
    coverage = per_genome * 2 * reads["read_len"] / lens
    return Sample(bases, lengths, mate, int(reads["insert_mean"]), genomes,
                  coverage)


def _apportion(total: int, share: np.ndarray) -> np.ndarray:
    """Whole counts in proportion to `share` that sum to `total` (largest
    remainders)."""
    raw = total * share
    out = np.floor(raw).astype(np.int64)
    out[np.argsort(out - raw)[:total - out.sum()]] += 1
    return out


def _pairs(rng, genomes, gid, *, read_len, insert_mean, insert_sd,
           err_rate):
    """fr pairs: read 2i from the fragment's start, 2i+1 the reverse
    complement of its end; the whole fragment flips strand with p = 1/2.

    Fragment lengths are N(insert_mean, insert_sd), rounded, so mates of a
    fragment shorter than two reads overlap, as in the source's 2x150 bp
    library with 270 bp inserts.  Only a fragment shorter than one read
    (4.4 s.d. below 270 at s.d. 27: about 1 in 200,000) is lengthened to
    a read's length."""
    L = read_len
    glen = np.array([len(g) for g in genomes])
    offset = np.concatenate([[0], np.cumsum(glen)[:-1]])
    cat = np.concatenate(genomes)
    pairs = len(gid)
    insert = np.maximum(L, np.rint(rng.normal(insert_mean, insert_sd, pairs))
                        .astype(np.int64))
    insert = np.minimum(insert, glen[gid])
    start = rng.integers(0, glen[gid] - insert + 1)
    flip = rng.integers(0, 2, size=pairs).astype(bool)
    i = np.arange(L)[None, :]
    head = cat[(offset[gid] + start)[:, None] + i]
    tail = COMPLEMENT[cat[(offset[gid] + start + insert - 1)[:, None] - i]]
    r1 = np.where(flip[:, None], tail, head)
    r2 = np.where(flip[:, None], head, tail)
    bases = np.empty((2 * pairs, L), np.uint8)
    bases[0::2], bases[1::2] = r1, r2
    err = rng.random(bases.shape) < err_rate
    bases[err] = (bases[err] + rng.integers(1, 4, size=int(err.sum()))) % 4
    lengths = np.full((2 * pairs,), L, np.int32)
    mate = np.arange(2 * pairs, dtype=np.int32) ^ 1
    return bases, lengths, mate
