"""Local assembly (mer-walking) extends contigs into read-covered flanks."""
import math
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import alignment, local_assembly
from repro.core.types import ContigSet
from repro.data import mgsim
from helpers import matches_genome, seq_str


def _contig_set(seqs, Lmax=1024, cap=8):
    bases = np.full((cap, Lmax), 4, np.uint8)
    lengths = np.zeros((cap,), np.int32)
    for i, s in enumerate(seqs):
        bases[i, : len(s)] = s
        lengths[i] = len(s)
    return ContigSet(
        bases=jnp.asarray(bases),
        lengths=jnp.asarray(lengths),
        depths=jnp.ones((cap,), jnp.float32) * 10,
    )


def test_walk_extends_contig_both_directions():
    genome, reads, _ = mgsim.single_genome_reads(21, genome_len=400, coverage=25)
    # truncated contig: genome[80:320]
    contigs = _contig_set([np.asarray(genome)[80:320]])
    alive = jnp.asarray(np.array([True] + [False] * 7))
    idx = alignment.build_seed_index(contigs, alive, seed_len=21, capacity=1 << 12)
    al = alignment.align_reads(reads, contigs, idx, seed_len=21)
    extended, walk = local_assembly.extend_contigs(
        reads, contigs, alive, al.contig[:, 0],
        mer_sizes=(17, 21, 25), capacity=1 << 14, max_ext=100,
    )
    new_len = int(extended.lengths[0])
    old_len = 240
    assert new_len > old_len + 40, f"extension too small: {new_len}"
    out = np.asarray(extended.bases[0, :new_len])
    assert matches_genome(out, genome), (
        "extended contig diverged from genome:\n"
        f"got    {seq_str(out)[:80]}...\n"
    )


def test_walk_stops_at_genome_end():
    genome, reads, _ = mgsim.single_genome_reads(22, genome_len=300, coverage=25)
    contigs = _contig_set([np.asarray(genome)[: 280]])
    alive = jnp.asarray(np.array([True] + [False] * 7))
    idx = alignment.build_seed_index(contigs, alive, seed_len=21, capacity=1 << 12)
    al = alignment.align_reads(reads, contigs, idx, seed_len=21)
    extended, walk = local_assembly.extend_contigs(
        reads, contigs, alive, al.contig[:, 0], max_ext=100, capacity=1 << 14
    )
    # cannot extend more than the genome has (20 right, 0 left)
    assert int(extended.lengths[0]) <= 302
    out = np.asarray(extended.bases[0, : int(extended.lengths[0])])
    assert matches_genome(out, genome)


def test_walk_isolation_between_contigs():
    """Mers are keyed by (contig, mer): reads of contig A must not extend
    contig B (the paper's isolation argument)."""
    rng = np.random.default_rng(23)
    gA = mgsim.random_genome(rng, 300)
    gB = mgsim.random_genome(rng, 300)
    commA = mgsim.Community(genomes=[gA], abundances=np.array([1.0]))
    readsA, _ = mgsim.generate_reads(24, commA, num_pairs=120, read_len=60)
    contigs = _contig_set([gA[:250], gB[:250]])
    alive = jnp.asarray(np.array([True, True] + [False] * 6))
    idx = alignment.build_seed_index(contigs, alive, seed_len=21, capacity=1 << 12)
    al = alignment.align_reads(readsA, contigs, idx, seed_len=21)
    extended, walk = local_assembly.extend_contigs(
        readsA, contigs, alive, al.contig[:, 0], max_ext=60, capacity=1 << 14
    )
    # contig A extends (reads cover its flank), contig B must not
    assert int(extended.lengths[0]) > 250
    assert int(extended.lengths[1]) == 250


# --- the graft (apply_extensions) -------------------------------------------

GRAFT_C, GRAFT_LMAX, GRAFT_EXT = 16, 256, 64


def _numpy_graft(bases, lengths, alive, ext_bases, ext_len):
    """Plain graft: RC'd left walk + contig + right walk, cut at Lmax, pad 4
    after; dead rows untouched."""
    C, Lmax = bases.shape
    out, new_len = bases.copy(), lengths.copy()
    for c in np.flatnonzero(alive):
        nL, nR = ext_len[c], ext_len[C + c]
        left = [3 - b if b < 4 else b for b in ext_bases[c, :nL][::-1]]
        row = np.concatenate([np.asarray(left, np.uint8),
                              bases[c, : lengths[c]],
                              ext_bases[C + c, :nR]])[:Lmax]
        out[c] = 4
        out[c, : len(row)] = row
        new_len[c] = len(row)
    return out, new_len


def _graft_case(case, seed):
    C, Lmax, E = GRAFT_C, GRAFT_LMAX, GRAFT_EXT
    if case == "rows_narrower_than_walk":
        Lmax = E // 2
    rng = np.random.default_rng(seed)
    lengths = rng.integers(0, Lmax + 1, C).astype(np.int32)
    ext_len = rng.integers(0, E + 1, 2 * C).astype(np.int32)
    alive = rng.random(C) < 0.75
    if case == "empty_contig":
        lengths[: C // 2] = 0
    elif case == "full_contig":       # growth clipped at Lmax
        lengths[: C // 2] = Lmax
    elif case == "left_walk_max":
        ext_len[:C] = E
    elif case == "right_walk_past_end":
        lengths[:] = rng.integers(Lmax - E, Lmax + 1, C)
        ext_len[C:] = E
    elif case == "dead_rows":
        alive[: C // 2] = False
    elif case == "no_walk":
        ext_len[:] = 0
    alive[0] = True
    # contig bases, then junk past each length: dead rows must keep it, live
    # rows must end in pad bases
    bases = rng.integers(0, 5, (C, Lmax)).astype(np.uint8)
    ext_bases = rng.integers(0, 5, (2 * C, E)).astype(np.uint8)
    depths = rng.random(C).astype(np.float32)
    return bases, lengths, alive, ext_bases, ext_len, depths


@pytest.mark.parametrize("case", [
    "random", "empty_contig", "full_contig", "left_walk_max",
    "right_walk_past_end", "dead_rows", "no_walk", "rows_narrower_than_walk",
])
def test_apply_extensions_matches_numpy_graft(case):
    bases, lengths, alive, ext_bases, ext_len, depths = _graft_case(case, 7)
    contigs = ContigSet(bases=jnp.asarray(bases), lengths=jnp.asarray(lengths),
                        depths=jnp.asarray(depths))
    walk = local_assembly.WalkResult(
        ext_bases=jnp.asarray(ext_bases), ext_len=jnp.asarray(ext_len),
        status=jnp.zeros(ext_len.shape, jnp.int32))
    got = local_assembly.apply_extensions(contigs, jnp.asarray(alive), walk)
    want_bases, want_len = _numpy_graft(bases, lengths, alive, ext_bases,
                                        ext_len)
    assert got.bases.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(got.lengths), want_len)
    np.testing.assert_array_equal(np.asarray(got.bases), want_bases)
    np.testing.assert_array_equal(np.asarray(got.depths), depths)
    got_bases = np.asarray(got.bases)
    np.testing.assert_array_equal(got_bases[~alive], bases[~alive])
    Lmax = bases.shape[1]
    pad = np.arange(Lmax)[None, :] >= want_len[:, None]
    assert (got_bases[alive & (want_len < Lmax)][
        pad[alive & (want_len < Lmax)]] == 4).all()


def _index_counts(hlo_text):
    """(op, index tuples) of every gather and scatter in optimised HLO."""
    shapes = dict(re.findall(r"(%[\w.\-]+) = \w+\[([\d,]*)\]", hlo_text))
    counts = []
    for op, indices, ivd in re.findall(
            r"= \S+ (gather|scatter)\(%[\w.\-]+, (%[\w.\-]+).*?"
            r"index_vector_dim=(\d+)", hlo_text):
        dims = [int(d) for d in shapes[indices].split(",") if d]
        n = math.prod(dims)
        counts.append((op, n // dims[int(ivd)] if int(ivd) < len(dims) else n))
    return counts


def test_apply_extensions_indexes_no_padded_position():
    """No gather or scatter of the graft indexes all C x Lmax positions:
    XLA:TPU runs those element by element, seconds a call at full size.
    And no scatter at all: a uint8 scatter of the right walks wrote wrong
    bases on a TPU v5e, which no CPU run shows."""
    C, Lmax, E = 256, 4096, 64
    contigs = ContigSet(bases=jnp.zeros((C, Lmax), jnp.uint8),
                        lengths=jnp.zeros((C,), jnp.int32),
                        depths=jnp.zeros((C,), jnp.float32))
    walk = local_assembly.WalkResult(
        ext_bases=jnp.zeros((2 * C, E), jnp.uint8),
        ext_len=jnp.zeros((2 * C,), jnp.int32),
        status=jnp.zeros((2 * C,), jnp.int32))
    alive = jnp.ones((C,), bool)
    hlo = local_assembly.apply_extensions.lower(
        contigs, alive, walk).compile().as_text()
    counts = _index_counts(hlo)
    assert all(n < C * Lmax for _, n in counts), counts
    assert all(op != "scatter" for op, _ in counts), counts
    # the reading finds a per-position gather where there is one
    per_position = jax.jit(
        lambda b, s: jnp.take_along_axis(
            b, jnp.clip(jnp.arange(Lmax)[None, :] - s[:, None], 0), axis=1))
    hlo = per_position.lower(contigs.bases, contigs.lengths).compile().as_text()
    assert ("gather", C * Lmax) in _index_counts(hlo)
