"""Scaffolding + rendering invariants (Algorithm 3 structural properties).

Property-based checks that hold for ANY link input, not just happy-path
fixtures:

  * every scaffold member is an alive, non-suspended contig, and no contig
    appears in more than one scaffold slot;
  * adjacent members are justified by a surviving link whose ends are
    consistent with the members' orientations (exit end of the left member
    paired with the entry end of the right member);
  * rendered scaffolds contain each member's oriented bases verbatim at
    its offset; flanks that overlap join on the overlap, read errors
    and all; other unclosed gaps render as N runs; gap-closed sequences keep
    both flanking contig ends verbatim with a non-N walk fill between
    them.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import gap_closing, local_assembly, scaffolding
from repro.core.types import ContigSet
from repro.data import mgsim


def _contig_set(seqs, Lmax=512, cap=16):
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


def _oriented(contigs, cid, orient):
    seq = np.asarray(contigs.bases[cid, : int(contigs.lengths[cid])])
    if orient == 1:
        seq = (3 - seq[::-1]) % 4
        seq = seq.astype(np.uint8)
    return seq


def _check_structure(scaffs, links, alive, suspended):
    """Invariants 1 + 2 on a Scaffolds result."""
    sc = np.asarray(scaffs.contig)
    orient = np.asarray(scaffs.orient)
    nm = np.asarray(scaffs.n_members)
    alive = np.asarray(alive)
    suspended = np.asarray(suspended)
    la = np.asarray(links.end_a)
    lb = np.asarray(links.end_b)
    lv = np.asarray(links.valid)
    link_pairs = {
        (int(min(a, b)), int(max(a, b)))
        for a, b, v in zip(la, lb, lv) if v and a >= 0 and b >= 0
    }
    seen = set()
    for s in range(sc.shape[0]):
        members = [(int(c), int(o))
                   for c, o in zip(sc[s], orient[s]) if c >= 0]
        assert len(members) == nm[s]
        for c, _ in members:
            assert alive[c], f"scaffold {s} member {c} is dead"
            assert not suspended[c], f"scaffold {s} member {c} is suspended"
            assert c not in seen, f"contig {c} placed twice"
            seen.add(c)
        for (c0, o0), (c1, o1) in zip(members, members[1:]):
            exit0 = c0 * 2 + (1 if o0 == 0 else 0)
            entry1 = c1 * 2 + (0 if o1 == 0 else 1)
            pair = (min(exit0, entry1), max(exit0, entry1))
            assert pair in link_pairs, (
                f"adjacent members {c0}(o{o0})->{c1}(o{o1}) of scaffold {s} "
                f"lack a supporting link for ends {pair}"
            )


def test_scaffold_structure_invariants_property():
    """Random witness soup -> scaffolds must still be structurally sound."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    C = 16

    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        n_witness=st.integers(1, 120),
        alive_frac=st.floats(0.2, 1.0),
    )
    def inner(seed, n_witness, alive_frac):
        rng = np.random.default_rng(seed)
        lengths = rng.integers(40, 400, size=(C,)).astype(np.int32)
        alive = jnp.asarray(rng.random((C,)) < alive_frac)
        contigs = ContigSet(
            bases=jnp.zeros((C, 8), jnp.uint8),
            lengths=jnp.asarray(lengths),
            depths=jnp.ones((C,), jnp.float32),
        )
        ea = jnp.asarray(rng.integers(0, 2 * C, size=(n_witness,)), jnp.int32)
        eb = jnp.asarray(rng.integers(0, 2 * C, size=(n_witness,)), jnp.int32)
        lo = jnp.minimum(ea, eb)
        hi = jnp.maximum(ea, eb)
        gap = jnp.asarray(rng.normal(20, 40, size=(n_witness,)), jnp.float32)
        valid = jnp.asarray(rng.random((n_witness,)) < 0.9) & (lo // 2 != hi // 2)
        is_splint = jnp.asarray(rng.random((n_witness,)) < 0.5)
        links = scaffolding.links_from_candidates(
            lo, hi, gap, valid, is_splint, alive, capacity=64, min_support=2
        )
        scaffs, links2, suspended, _ = scaffolding.scaffold_from_links(
            links, contigs, alive, 180.0, max_members=8
        )
        _check_structure(scaffs, links2, alive, suspended)

    inner()


def _two_contig_scaffold(gap_est=30.0, cap=16):
    """A hand-built scaffold [contig0 fwd, contig1 rc] for render tests."""
    S, M = cap, 4
    sc = np.full((S, M), -1, np.int32)
    orient = np.zeros((S, M), np.uint8)
    gap = np.zeros((S, M), np.float32)
    nm = np.zeros((S,), np.int32)
    sc[0, 0], sc[0, 1] = 0, 1
    orient[0, 1] = 1
    gap[0, 0] = gap_est
    nm[0] = 2
    return scaffolding.Scaffolds(
        contig=jnp.asarray(sc), orient=jnp.asarray(orient),
        gap=jnp.asarray(gap), n_members=jnp.asarray(nm),
        n_scaffolds=jnp.int32(1),
    )


def test_render_members_verbatim_open_gap_is_n_run():
    """With EMPTY walk tables nothing can close: members must still render
    verbatim around an N run sized by the gap estimate."""
    rng = np.random.default_rng(11)
    gA = mgsim.random_genome(rng, 200)
    gB = mgsim.random_genome(rng, 150)
    contigs = _contig_set([gA, gB])
    scaffs = _two_contig_scaffold(gap_est=23.0)
    mer_sizes = (17, 21, 25)
    wt = local_assembly.empty_walk_tables(mer_sizes=mer_sizes, capacity=1 << 10)
    seqs = gap_closing.close_and_render_with_tables(
        scaffs, contigs, wt, seed_len=17, mer_sizes=mer_sizes
    )
    assert not bool(np.asarray(seqs.closed).any())
    L = int(seqs.lengths[0])
    out = np.asarray(seqs.bases[0, :L])
    left = _oriented(contigs, 0, 0)
    right = _oriented(contigs, 1, 1)
    assert L == len(left) + 23 + len(right)
    np.testing.assert_array_equal(out[: len(left)], left)
    np.testing.assert_array_equal(out[len(left): len(left) + 23], 4)
    np.testing.assert_array_equal(out[len(left) + 23:], right)


def _overlap_pair(seed, overlap, errors):
    """Contigs [genome[:250], rc(genome[250 - overlap:400])] with read
    errors at the given offsets of the second contig's overlapping head."""
    genome = mgsim.random_genome(np.random.default_rng(seed), 450)
    left, right = genome[:250], genome[250 - overlap: 400].copy()
    for e in errors:
        right[e] = (right[e] + 1) % 4
    # contig 1 is stored reverse-complemented; the scaffold flips it back
    contigs = _contig_set([left, mgsim.rc_np(right)])
    mer_sizes = (17, 21, 25)
    wt = local_assembly.empty_walk_tables(mer_sizes=mer_sizes, capacity=1 << 10)
    seqs = gap_closing.close_and_render_with_tables(
        _two_contig_scaffold(gap_est=-float(overlap) - 20), contigs, wt,
        seed_len=17, mer_sizes=mer_sizes,
    )
    L = int(seqs.lengths[0])
    return left, right, bool(seqs.closed[0, 0]), np.asarray(seqs.bases[0, :L])


@pytest.mark.parametrize("overlap,errors", [
    (17, ()), (90, ()), (148, ()),
    (60, (0,)), (60, (59,)), (120, (3, 100)),
])
def test_overlapping_flanks_render_the_overlap_once(overlap, errors):
    """Adjacent contigs whose ends overlap (k - 1 plus what local assembly
    walked onto both) join on the overlap: no N run and no duplicated
    bases, even with nothing to walk.  An overlap that differs in a read
    error or two still joins, with the right flank's bases."""
    left, right, closed, out = _overlap_pair(14, overlap, errors)
    assert closed
    np.testing.assert_array_equal(
        out, np.concatenate([left[: len(left) - overlap], right])
    )


def test_inexact_overlap_stays_an_n_run():
    """An overlap that differs in more than a couple of bases is not
    joined: both members render verbatim around a one-base N run."""
    left, right, closed, out = _overlap_pair(15, 60, (5, 30, 55))
    assert not closed
    assert len(out) == len(left) + 1 + len(right)
    np.testing.assert_array_equal(out[: len(left)], left)
    assert out[len(left)] == 4
    np.testing.assert_array_equal(out[len(left) + 1:], right)


def test_closed_gap_keeps_flanking_ends_verbatim():
    """A walk-closed gap: both flanks verbatim, the fill free of Ns, and
    the whole rendered region equal to the underlying genome."""
    rng = np.random.default_rng(12)
    genome = mgsim.random_genome(rng, 500)
    comm = mgsim.Community(genomes=[genome], abundances=np.array([1.0]))
    reads, _ = mgsim.generate_reads(13, comm, num_pairs=400, read_len=60)
    contigs = _contig_set([genome[:200], genome[230:430]])
    alive = jnp.asarray([True, True] + [False] * 14)
    from repro.core import alignment

    idx = alignment.build_seed_index(contigs, alive, seed_len=21,
                                     capacity=1 << 12)
    al = alignment.align_reads(reads, contigs, idx, seed_len=21)
    scaffs = _two_contig_scaffold(gap_est=30.0)
    # member 1 forward this time (genome orientation)
    scaffs = scaffs._replace(orient=jnp.zeros_like(scaffs.orient))
    seqs = gap_closing.close_and_render(
        scaffs, contigs, reads, al.contig[:, 0],
        seed_len=17, mer_sizes=(17, 21, 25), walk_capacity=1 << 14,
    )
    closed = np.asarray(seqs.closed)
    assert closed[0, 0], "covered 30bp gap must close"
    L = int(seqs.lengths[0])
    out = np.asarray(seqs.bases[0, :L])
    left = _oriented(contigs, 0, 0)
    right = _oriented(contigs, 1, 0)
    fill_len = L - len(left) - len(right)
    assert 0 <= fill_len <= 64
    # flanks verbatim, fill is real sequence (no Ns)
    np.testing.assert_array_equal(out[: len(left)], left)
    np.testing.assert_array_equal(out[len(left) + fill_len:], right)
    assert (out[len(left): len(left) + fill_len] < 4).all()
    # and in this covered fixture the closure is exactly the genome
    np.testing.assert_array_equal(out, genome[:430])


def test_scaffold_structure_on_real_assembly():
    """Invariants 1 + 2 on a real end-to-end assembly (no hypothesis)."""
    from repro.api import Assembler, AssemblyPlan, Local

    comm = mgsim.sample_community(19, num_genomes=2, genome_len=300,
                                  abundance_sigma=0.3)
    reads, _ = mgsim.generate_reads(20, comm, num_pairs=300, read_len=60,
                                    err_rate=0.003)
    plan = AssemblyPlan.from_dataset(reads, (17, 21, 4), unique_rate=0.2)
    out = Assembler(plan, Local()).assemble(reads)
    _check_structure(out["scaffolds"], out["links"], out["alive"],
                     out["suspended"])
    # rendered scaffolds: every member's oriented bases appear verbatim
    seqs = out["scaffold_seqs"]
    sc = np.asarray(out["scaffolds"].contig)
    orient = np.asarray(out["scaffolds"].orient)
    contigs = out["contigs"]
    for s in range(sc.shape[0]):
        L = int(seqs.lengths[s])
        if L == 0:
            continue
        row = np.asarray(seqs.bases[s, :L]).tobytes()
        for c, o in zip(sc[s], orient[s]):
            if c < 0:
                continue
            member = _oriented(contigs, int(c), int(o)).tobytes()
            assert member in row, (s, int(c))
