"""The plain reference that decides `correct`.

Each function is a straightforward numpy (or plain Python) statement of one
guarantee the configuration states, applied to what the timed assembly
produced.  Nothing here imports the program or takes anything it made but
its answers: the reads are the benchmark's own, and every limit comes from
the configuration's `guarantees`.

An assembly is a plain dict of host arrays (see `run.py`'s `host_result`):

  rounds      one dict per k: k, n_kmers (the program's count), and the
              round's contigs after local assembly (bases, lengths, alive)
  alignments  the final alignment: contig, cstart, orient, matches, overlap
              ([R, 2] each)
  scaffolds   contig, orient, gap ([S, M]), n_members ([S]), n_scaffolds
  seqs        rendered scaffolds: bases [S, W], lengths [S], closed [S, M]
  suspended   [C] contigs suspended as repeats
  overflow    every overflow counter of the run, by name
"""
from __future__ import annotations

import numpy as np

from . import quality

COMPLEMENT = np.array([3, 2, 1, 0, 4], dtype=np.uint8)


def canonical_kmers(rows: np.ndarray, lengths: np.ndarray, k: int):
    """Canonical codes of every k-window inside a row's length with no N."""
    rows = np.asarray(rows)
    R, L = rows.shape
    n = L - k + 1
    if R == 0 or n <= 0:
        return np.zeros((0,), np.uint64)
    ok = (rows < 4) & (np.arange(L)[None, :] < np.asarray(lengths)[:, None])
    bad = np.concatenate([np.zeros((R, 1), np.int64),
                          np.cumsum(~ok, axis=1)], axis=1)
    window_ok = (bad[:, k:] - bad[:, :n]) == 0
    b = (rows & 3).astype(np.uint64)
    fwd = np.zeros((R, n), np.uint64)
    rev = np.zeros((R, n), np.uint64)
    for j in range(k):
        bj = b[:, j:j + n]
        fwd = (fwd << np.uint64(2)) | bj
        rev |= (np.uint64(3) - bj) << np.uint64(2 * j)
    return np.minimum(fwd, rev)[window_ok]


def live_rows(contigs: dict):
    """Bases and lengths of the live contigs, cut to the longest."""
    keep = np.asarray(contigs["alive"]) & (np.asarray(contigs["lengths"]) > 0)
    lengths = np.asarray(contigs["lengths"])[keep]
    width = int(lengths.max()) if lengths.size else 0
    return np.asarray(contigs["bases"])[keep, :width], lengths


def kmer_counts(asm: dict, reads: dict, g: dict):
    """Round by round: the solid k-mers the reference counts, against the
    program's count; and the k-mers inside the round's contigs that are not
    solid.

    A k-mer is solid when the reads hold it `min_count` times or more, or
    when it lies in a live contig of the previous round (those enter the
    next round pseudo-counted with a weight of at least `min_count`).  A
    contig is a walk over solid k-mers, which local assembly then extends
    by up to `max_ext` bases at each end from the reads; the k-mers of
    those ends are not held to it."""
    count_diff, unsolid = 0, 0
    prev = None
    for rnd in asm["rounds"]:
        k = rnd["k"]
        codes, counts = np.unique(
            canonical_kmers(reads["bases"], reads["lengths"], k),
            return_counts=True)
        solid = codes[counts >= g["min_count"]]
        if prev is not None:
            solid = np.union1d(
                solid, np.unique(canonical_kmers(*live_rows(prev), k)))
        count_diff += abs(int(rnd["n_kmers"]) - int(solid.size))
        rows, lengths = live_rows(rnd)
        e = g["max_ext"]
        inner = np.full_like(rows, 4)
        for i, n in enumerate(lengths):
            inner[i, e:n - e] = rows[i, e:n - e]
        unsolid += int((~np.isin(canonical_kmers(inner, lengths, k),
                                 solid)).sum())
        prev = rnd
    return count_diff, unsolid


def alignment_errors(asm: dict, reads: dict, g: dict) -> int:
    """Placements whose recomputed matches or overlap differ from the
    program's, that fall short of the identity floor or the seed length,
    or that land on a contig that is not live."""
    al = asm["alignments"]
    final = asm["rounds"][-1]
    clen = np.where(final["alive"], final["lengths"], 0)
    cb = np.asarray(final["bases"])
    rb = np.asarray(reads["bases"])
    rl = np.asarray(reads["lengths"])
    R, L = rb.shape
    i = np.arange(L)[None, :]
    errors = 0
    for s in range(al["contig"].shape[1]):
        c = al["contig"][:, s]
        hit = c >= 0
        known = c < clen.shape[0]
        c_ = np.where(hit & known, c, 0)
        start = al["cstart"][:, s].astype(np.int64)[:, None]
        rc = al["orient"][:, s][:, None] == 1
        cpos = np.where(rc, start + (rl[:, None] - 1 - i), start + i)
        inside = (cpos >= 0) & (cpos < clen[c_][:, None]) & (i < rl[:, None])
        cbase = cb[c_[:, None], np.clip(cpos, 0, cb.shape[1] - 1)]
        rbase = np.where(rc, COMPLEMENT[rb], rb)
        matches = (inside & (cbase == rbase) & (rb < 4)).sum(axis=1)
        overlap = inside.sum(axis=1)
        bad = ((matches != al["matches"][:, s])
               | (overlap != al["overlap"][:, s])
               | (matches < g["min_identity"] * np.maximum(overlap, 1))
               | (overlap < g["align_seed_len"])
               | (clen[c_] == 0) | ~known)
        errors += int((hit & bad).sum())
    return errors


def _end(contig: int, orient: int, leaving: bool) -> int:
    """Packed end id (contig * 2 + end) where a scaffold member is left
    (`leaving`) or entered, in the member's orientation."""
    right = (orient == 0) == leaving
    return 2 * contig + int(right)


def link_witnesses(asm: dict, reads: dict) -> dict:
    """(end a, end b) -> [witnesses, gap sum] from the final alignment.

    A splint is one read whose two placements lie on two contigs, with the
    bridge between them shorter than a read; a span is a pair whose mates
    lie on two contigs, with the gap the insert size leaves between them
    under twice the insert size.  Only live contigs count."""
    al = asm["alignments"]
    final = asm["rounds"][-1]
    alive = np.asarray(final["alive"])
    clen = np.where(alive, final["lengths"], 0)
    rl = np.asarray(reads["lengths"])
    mate = np.asarray(reads["mate"])
    Lmax = np.asarray(reads["bases"]).shape[1]
    ins = float(reads["insert_size"])
    out = {}

    def add(e1, e2, gap):
        if max(e1, e2) < 2 * alive.shape[0] and alive[e1 // 2] \
                and alive[e2 // 2]:
            w = out.setdefault((min(e1, e2), max(e1, e2)), [0, 0.0])
            w[0] += 1
            w[1] += gap

    def interval(r, s):
        c, st = int(al["contig"][r, s]), int(al["cstart"][r, s])
        c = min(c, clen.shape[0] - 1)
        if al["orient"][r, s] == 0:
            return -st, int(clen[c]) - st
        return int(rl[r]) - st - int(clen[c]), int(rl[r]) - st

    for r in range(rl.shape[0]):
        c0, c1 = int(al["contig"][r, 0]), int(al["contig"][r, 1])
        if c0 >= 0 and c1 >= 0 and c0 != c1:
            (a0, b0), (a1, b1) = interval(r, 0), interval(r, 1)
            f, s_ = (0, 1) if a0 <= a1 else (1, 0)
            gap = a1 - b0 if f == 0 else a0 - b1
            if -Lmax < gap < Lmax:
                cf, cs = int(al["contig"][r, f]), int(al["contig"][r, s_])
                ef = 2 * cf + (1 if al["orient"][r, f] == 0 else 0)
                es = 2 * cs + (0 if al["orient"][r, s_] == 0 else 1)
                add(ef, es, float(gap))
        m = int(mate[r])
        if m > r:
            cr, cm = int(al["contig"][r, 0]), int(al["contig"][m, 0])
            if cr >= 0 and cm >= 0 and cr != cm:
                dist = []
                for x, c in ((r, cr), (m, cm)):
                    c = min(c, clen.shape[0] - 1)
                    st = int(al["cstart"][x, 0])
                    fwd = al["orient"][x, 0] == 0
                    dist.append(int(clen[c]) - st if fwd else st + int(rl[x]))
                gap = ins - dist[0] - dist[1]
                if -2 * ins < gap < 2 * ins:
                    er = 2 * cr + (1 if al["orient"][r, 0] == 0 else 0)
                    em = 2 * cm + (1 if al["orient"][m, 0] == 0 else 0)
                    add(er, em, gap)
    return out


def scaffold_errors(asm: dict, reads: dict, g: dict):
    """Joins, placement and the rendered scaffold sequences.

    Returns (joins without `min_link_support` witnesses or whose gap is not
    their witnesses' mean, live contigs not placed exactly once, rendered
    bases that differ from the reference's rendering)."""
    sc, seqs = asm["scaffolds"], asm["seqs"]
    final = asm["rounds"][-1]
    alive = np.asarray(final["alive"]) & ~np.asarray(asm["suspended"])
    lens = np.asarray(final["lengths"])
    cb = np.asarray(final["bases"])
    wit = link_witnesses(asm, reads)
    bad_joins, render_diff = 0, 0
    placed = np.zeros(lens.shape, np.int64)

    def oriented(c, o):
        b = cb[c, :lens[c]]
        return COMPLEMENT[b[::-1]] if o else b

    for s in range(int(sc["n_scaffolds"])):
        n = int(sc["n_members"][s])
        row = np.asarray(seqs["bases"][s, :int(seqs["lengths"][s])])
        members = [(int(sc["contig"][s, j]), int(sc["orient"][s, j]))
                   for j in range(n)]
        if any(not 0 <= c < lens.shape[0] for c, _ in members):
            # a member that is no contig renders nothing checkable
            render_diff += len(row) + 1
            continue
        for c, _ in members:
            placed[c] += 1
        expect = np.full((0,), 4, np.uint8)
        for j, (c, o) in enumerate(members):
            seq = oriented(c, o)
            if j == 0:
                expect = seq.copy()
                continue
            pc, po = members[j - 1]
            w = wit.get(tuple(sorted((_end(pc, po, True),
                                      _end(c, o, False)))))
            gap = float(sc["gap"][s, j - 1])
            if (w is None or w[0] < g["min_link_support"]
                    or abs(gap - w[1] / w[0]) > 1e-3 * max(1.0, abs(gap))):
                bad_joins += 1
            if not seqs["closed"][s, j - 1]:
                # an open gap renders as a run of N sized by the estimate
                run = int(np.clip(gap, 1, g["max_n_run"]))
                expect = np.concatenate([expect, np.full(run, 4, np.uint8),
                                         seq])
                continue
            off = _closed_offset(row, len(expect), seq, g)
            if off is None:
                render_diff += len(seq)
                expect = np.concatenate([expect, seq])
            elif off < len(expect):
                # flanks joined on their overlap: the right flank's bases
                o_len = len(expect) - off
                tail = expect[off:]
                if (o_len < g["min_overlap"] or o_len > g["max_overlap"]
                        or o_len > len(seq) or o_len > len(oriented(pc, po))
                        or int((tail != seq[:o_len]).sum())
                        > g["max_overlap_mismatches"]):
                    render_diff += o_len
                expect = np.concatenate([expect[:off], seq])
            else:
                fill = row[len(expect):off]
                if len(fill) > g["max_fill"] or (fill > 3).any():
                    render_diff += len(fill)
                expect = np.concatenate([expect, fill, seq])
        width = min(len(expect), len(row))
        render_diff += int((expect[:width] != row[:width]).sum())
        render_diff += abs(len(expect) - len(row))
    unplaced = int(((placed != 1) & alive & (lens > 0)).sum()
                   + (placed[~(alive & (lens > 0))]).sum())
    return bad_joins, unplaced, render_diff


def _closed_offset(row, end: int, seq, g):
    """Where the next member starts in the rendered row after a closed gap:
    on an overlap of up to `max_overlap` bases, or after a walked fill of
    up to `max_fill`.  The right flank is rendered whole from there on, but
    for what a further member overlaps, so the place is the one at which
    the row agrees with it longest; on a tie a fill wins (the program
    prefers a walk that reached the flank to an overlap), then the longest
    overlap."""
    best, best_len = None, 0
    lo = max(0, end - g["max_overlap"])
    for off in range(lo, end + g["max_fill"] + 1):
        part = row[off:off + len(seq)]
        same = part == seq[:len(part)]
        agree = len(part) if same.all() else int(np.argmin(same))
        if agree < g["min_overlap"]:
            continue
        if (agree > best_len or (agree == best_len and off >= end
                                 and best < end)):
            best, best_len = off, agree
    return best


def overflow_total(asm: dict) -> int:
    """Every overflow counter of the run: the context's accounting, each
    round's k-mer table and traversal flags, scaffolds cut at the plan's
    longest row."""
    return sum(int(v) for v in asm["overflow"].values())


def assembly_quality(asm: dict, genomes, coverage, g: dict) -> dict:
    """Genome fraction of the deep genomes and pieces with an extensive
    misassembly, by the metaQUAST stand-in, over the scaffolds of at least
    `min_piece` bases."""
    seqs = asm["seqs"]
    lens = np.asarray(seqs["lengths"])
    pieces = [np.asarray(seqs["bases"][i, :lens[i]])
              for i in range(int(seqs["n_scaffolds"]))
              if lens[i] >= g["min_piece"]]
    rep = quality.evaluate(pieces, genomes)
    fr = np.asarray(rep["genome_fractions"])
    deep = np.asarray(coverage) >= g["min_coverage"]
    return {"deep_fractions": fr[deep].tolist(), "pieces": rep["n_pieces"],
            "misassemblies": rep["misassemblies"]}


def check(asm: dict, reads: dict, genomes, coverage, g: dict) -> dict:
    """Every number compared for one assembly, by its short name."""
    count_diff, unsolid = kmer_counts(asm, reads, g)
    joins, unplaced, render = scaffold_errors(asm, reads, g)
    q = assembly_quality(asm, genomes, coverage, g)
    return {
        "overflow": overflow_total(asm),
        "kmer_count_diff": count_diff,
        "contig_kmers_unsolid": unsolid,
        "alignment_errors": alignment_errors(asm, reads, g),
        "joins_unsupported": joins,
        "contigs_unplaced": unplaced,
        "render_diff": render,
        "deep_genome_fraction_min": min(q["deep_fractions"], default=0.0),
        "misassembled_pieces": q["misassemblies"],
        "pieces": q["pieces"],
        "deep_fractions": q["deep_fractions"],
    }
