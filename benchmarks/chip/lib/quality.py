"""metaQUAST-style assembly quality metrics (Table I stand-in).

A copy of the program's `benchmarks/metrics.py`, kept with the benchmark
so that the yardstick cannot change with the program.  One change: a
relocation counts as a misassembly beyond metaQUAST's 1 kbp, not 100 bp.

Ground truth comes from MGSim, so genome fraction / misassembly calls are
exact rather than alignment-heuristic:
  * genome fraction: w-mer window coverage of each reference,
  * misassembly: a contig whose w-mers map to >1 genome, to both strands,
    or to positions on one genome more than 1 kbp out of line (metaQUAST's
    extensive misassembly),
  * contiguity: total length in pieces >= thresholds, N50/NGA-ish.
"""
from __future__ import annotations

import numpy as np

_RC = np.array([3, 2, 1, 0, 4], dtype=np.uint8)
_LETTERS = np.frombuffer(b"ACGTN", dtype=np.uint8)


def _s(seq):
    return _LETTERS[np.asarray(seq, dtype=np.uint8)].tobytes().decode()


def _rc(seq):
    return _RC[np.asarray(seq)[::-1]]


def build_ref_index(genomes, w=30):
    """w-mer -> (genome_id, pos, strand) (unique w-mers only)."""
    idx = {}
    dup = set()
    for gi, g in enumerate(genomes):
        s = _s(g)
        sr = _s(_rc(g))
        L = len(s)
        for strand, src in ((0, s), (1, sr)):
            for i in range(L - w + 1):
                key = src[i : i + w]
                pos = i if strand == 0 else L - w - i
                if key in idx or key in dup:
                    dup.add(key)
                    idx.pop(key, None)
                    continue
                idx[key] = (gi, pos, strand)
    return idx, dup


def contig_mappings(contig, ref_idx, w=30, stride=7):
    """Sampled w-mer hits of one contig against the reference index."""
    s = _s(contig)
    hits = []
    for i in range(0, max(1, len(s) - w + 1), stride):
        h = ref_idx.get(s[i : i + w])
        if h:
            hits.append((i,) + h)
    return hits


#: metaQUAST's extensive misassembly: a relocation of more than 1 kbp (its
#: --extensive-mis-size default), an inversion or a translocation.  A
#: shorter shift is a local misassembly, which it does not count.
EXTENSIVE_MIS_SIZE = 1000


def is_misassembled(hits, max_gap=EXTENSIVE_MIS_SIZE) -> bool:
    """metaQUAST's rule for an extensive misassembly: hits must be one
    genome, one strand, and collinear within max_gap."""
    if len(hits) < 2:
        return False
    genomes = {h[1] for h in hits}
    if len(genomes) > 1:
        return True
    strands = {h[3] for h in hits}
    if len(strands) > 1:
        return True
    strand = hits[0][3]
    for (i1, _, p1, _), (i2, _, p2, _) in zip(hits, hits[1:]):
        expect = (i2 - i1) if strand == 0 else (i1 - i2)
        if abs((p2 - p1) - expect) > max_gap:
            return True
    return False


def _windows(pieces, w=30) -> set:
    """Every w-mer of the pieces, on both strands."""
    windows = set()
    for c in pieces:
        for src in (_s(c), _s(_rc(c))):
            windows.update(src[i : i + w] for i in range(len(src) - w + 1))
    return windows


def genome_fraction(pieces, genome, w=30, windows=None) -> float:
    if windows is None:
        windows = _windows(pieces, w)
    g = _s(genome)
    n = len(g) - w + 1
    if n <= 0:
        return 0.0
    return sum(1 for i in range(n) if g[i : i + w] in windows) / n


def n50(lengths) -> int:
    ls = sorted((int(x) for x in lengths), reverse=True)
    total = sum(ls)
    acc = 0
    for L in ls:
        acc += L
        if acc * 2 >= total:
            return L
    return 0


def evaluate(pieces, genomes, w=30, length_thresholds=(100, 250, 500)):
    """Full Table-I style report for a list of assembled sequences."""
    ref_idx, _ = build_ref_index(genomes, w)
    lens = [len(p) for p in pieces]
    report = {
        "n_pieces": len(pieces),
        "total_len": int(sum(lens)),
        "n50": n50(lens),
    }
    for t in length_thresholds:
        report[f"len_ge_{t}"] = int(sum(L for L in lens if L >= t))
    mis = 0
    for p in pieces:
        hits = contig_mappings(p, ref_idx, w)
        if is_misassembled(hits):
            mis += 1
    report["misassemblies"] = mis
    windows = _windows(pieces, w)
    fracs = [genome_fraction(pieces, g, w, windows) for g in genomes]
    report["genome_fractions"] = [float(f) for f in fracs]
    report["genome_fraction"] = float(np.mean(fracs))
    report["genome_fraction_min"] = float(np.min(fracs))
    return report
