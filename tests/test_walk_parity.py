"""Walk kernel backend parity: pallas and ref must be BIT-identical.

`ops.mer_walk` is the traversal twin of the extraction hot path
(DESIGN.md §8): contig extension and gap closing on Local, Mesh, and the
streaming driver all ladder-walk through it.  These tests hold the
dispatch layer to its contract:

  * op-level: pallas and ref produce identical ext_bases / ext_len /
    status / hit / hit_pos over odd mer ladders in 3..31, ragged contig
    lengths (including ends shorter than the largest mer), saturated
    tables, fork-heavy tables (tiny mers), max-steps truncation, and the
    gap-closing target-stop variant;
  * pipeline-level: `assemble` and `assemble_stream` on Local produce
    bit-identical scaffolds under both backends (the Mesh(8) twin is
    `test_mesh_walk_backend_parity` in tests/test_mesh_backend_parity.py).
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.api import Assembler, AssemblyPlan, Local
from repro.core import kmer, local_assembly
from repro.core.types import ContigSet, ReadSet
from repro.data import mgsim
from repro.kernels import ops
from repro.stream.batches import batches_from_readset

WALK_LANES = ("ext_bases", "ext_len", "status", "hit", "hit_pos")


def _assert_walks_equal(got, want):
    for field in WALK_LANES:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)), np.asarray(getattr(want, field)),
            err_msg=field,
        )


def _random_tables(rng, mer_sizes, capacity, *, num_reads=64, read_len=None,
                   n_contigs=8):
    """WalkTables built from random reads with random contig assignments."""
    tag_bits = min(16, 62 - 2 * max(mer_sizes))
    L = read_len or (max(mer_sizes) + 20)
    bases = rng.integers(0, 4, size=(num_reads, L)).astype(np.uint8)
    bases[rng.random((num_reads, L)) < 0.02] = 4
    lengths = rng.integers(0, L + 1, size=(num_reads,)).astype(np.int32)
    reads = ReadSet(
        bases=jnp.asarray(bases), lengths=jnp.asarray(lengths),
        mate=jnp.full((num_reads,), -1, jnp.int32), insert_size=0,
    )
    read_contig = jnp.asarray(
        rng.integers(-1, n_contigs, size=(num_reads,)), jnp.int32
    )
    wt = local_assembly.build_walk_tables(
        reads, read_contig, mer_sizes=tuple(mer_sizes), tag_bits=tag_bits,
        capacity=capacity,
    )
    return wt, tag_bits


def _random_walkers(rng, E, n_contigs=8):
    """Random BUF_K suffix buffers, contig ids, and an active mask."""
    suffix = rng.integers(0, 4, size=(E, local_assembly.BUF_K)).astype(np.uint8)
    hi, lo = kmer.pack_window(jnp.asarray(suffix), k=local_assembly.BUF_K)
    contig = jnp.asarray(rng.integers(0, n_contigs, size=(E,)), jnp.int32)
    active = jnp.asarray(rng.random((E,)) < 0.8)
    return hi, lo, contig, active


def _walk_both(wt, hi, lo, contig, active, **kw):
    got = ops.mer_walk(wt, hi, lo, contig, active, backend="pallas", **kw)
    want = ops.mer_walk(wt, hi, lo, contig, active, backend="ref", **kw)
    _assert_walks_equal(got, want)
    return want


@pytest.mark.parametrize(
    "mer_sizes,capacity,max_ext,E",
    [
        ((17, 21, 25), 1 << 12, 32, 16),
        ((3, 5, 7), 1 << 10, 16, 8),     # tiny mers: fork/tie-heavy tables
        ((17, 21, 25), 16, 16, 8),       # saturated: capacity << occurrences
        ((21,), 1 << 10, 8, 13),         # single rung + awkward walker count
        ((29, 31), 1 << 10, 4, 8),       # k=31 (tag_bits=0) + truncation
    ],
)
def test_walk_backends_bit_identical(mer_sizes, capacity, max_ext, E):
    rng = np.random.default_rng(max_ext * 101 + E + max(mer_sizes))
    wt, tag_bits = _random_tables(rng, mer_sizes, capacity)
    hi, lo, contig, active = _random_walkers(rng, E)
    _walk_both(wt, hi, lo, contig, active, mer_sizes=mer_sizes,
               tag_bits=tag_bits, max_ext=max_ext)


def test_walk_real_extension_parity_and_truncation():
    """On a real single-genome fixture the walk must actually extend, the
    backends must agree bit-for-bit, and max_ext must truncate exactly."""
    genome, reads, _ = mgsim.single_genome_reads(
        33, genome_len=400, coverage=25
    )
    cap, Lmax = 8, 1024
    bases = np.full((cap, Lmax), 4, np.uint8)
    seg = np.asarray(genome)[80:320]
    bases[0, : len(seg)] = seg
    contigs = ContigSet(
        bases=jnp.asarray(bases),
        lengths=jnp.asarray([len(seg)] + [0] * (cap - 1), jnp.int32),
        depths=jnp.ones((cap,), jnp.float32),
    )
    alive = jnp.asarray([True] + [False] * (cap - 1))
    read_contig = jnp.zeros((reads.num_reads,), jnp.int32)
    mer_sizes = (17, 21, 25)
    tag_bits = min(16, 62 - 2 * max(mer_sizes))
    wt = local_assembly.build_walk_tables(
        reads, read_contig, mer_sizes=mer_sizes, tag_bits=tag_bits,
        capacity=1 << 14,
    )
    bhi, blo, act = local_assembly.contig_end_buffers(contigs, alive)
    wc = jnp.concatenate([jnp.arange(cap), jnp.arange(cap)]).astype(jnp.int32)
    full = _walk_both(wt, bhi, blo, wc, act, mer_sizes=mer_sizes,
                      tag_bits=tag_bits, max_ext=64)
    assert int(full.ext_len.max()) > 20, "fixture must actually walk"
    short = _walk_both(wt, bhi, blo, wc, act, mer_sizes=mer_sizes,
                       tag_bits=tag_bits, max_ext=5)
    # truncation: the short walk is a prefix of the long one, still ACTIVE
    np.testing.assert_array_equal(
        np.asarray(short.ext_bases),
        np.asarray(full.ext_bases[:, :5]),
    )
    long_walkers = np.asarray(full.ext_len) >= 5
    assert (np.asarray(short.status)[long_walkers]
            == local_assembly.ACTIVE).all()


def test_walk_target_stop_parity():
    """Gap-walk variant: a walker whose suffix reaches the target seed
    halts with HIT at the first-match position, identically per backend."""
    genome, reads, _ = mgsim.single_genome_reads(34, genome_len=400,
                                                 coverage=25)
    cap, Lmax = 8, 1024
    bases = np.full((cap, Lmax), 4, np.uint8)
    seg = np.asarray(genome)[:200]
    bases[0, : len(seg)] = seg
    contigs = ContigSet(
        bases=jnp.asarray(bases),
        lengths=jnp.asarray([200] + [0] * (cap - 1), jnp.int32),
        depths=jnp.ones((cap,), jnp.float32),
    )
    alive = jnp.asarray([True] + [False] * (cap - 1))
    mer_sizes = (17, 21, 25)
    tag_bits = min(16, 62 - 2 * max(mer_sizes))
    wt = local_assembly.build_walk_tables(
        reads, jnp.zeros((reads.num_reads,), jnp.int32),
        mer_sizes=mer_sizes, tag_bits=tag_bits, capacity=1 << 14,
    )
    bhi, blo, _ = local_assembly.contig_end_buffers(contigs, alive)
    tail_hi, tail_lo = bhi[cap:][:1], blo[cap:][:1]  # contig 0 right end
    seed_len = 17
    # target: the genome seed 30 bases past the contig end -> real hit
    t_hi, t_lo = kmer.pack_window(
        jnp.asarray(np.asarray(genome)[230:230 + seed_len][None, :]),
        k=seed_len,
    )
    kw = dict(mer_sizes=mer_sizes, tag_bits=tag_bits, max_ext=64,
              target_hi=t_hi, target_lo=t_lo, seed_len=seed_len)
    one = jnp.asarray([0], jnp.int32)
    on = jnp.asarray([True])
    got = ops.mer_walk(wt, tail_hi, tail_lo, one, on, backend="pallas", **kw)
    want = ops.mer_walk(wt, tail_hi, tail_lo, one, on, backend="ref", **kw)
    _assert_walks_equal(got, want)
    assert bool(want.hit[0]), "target 30bp out must be reachable"
    # suffix matches after accepting gap + seed_len bases
    assert int(want.hit_pos[0]) == 30 + seed_len
    assert int(want.status[0]) == local_assembly.HIT
    # the walker STOPPED at the hit: no bases accepted past hit_pos
    assert int(want.ext_len[0]) == int(want.hit_pos[0])
    # a miss target never hits, and the un-targeted walk is unaffected
    miss_hi = t_hi ^ jnp.uint32(0x5)
    got2 = ops.mer_walk(wt, tail_hi, tail_lo, one, on, backend="pallas",
                        **{**kw, "target_hi": miss_hi})
    want2 = ops.mer_walk(wt, tail_hi, tail_lo, one, on, backend="ref",
                         **{**kw, "target_hi": miss_hi})
    _assert_walks_equal(got2, want2)
    assert not bool(want2.hit[0])


def test_walk_backend_parity_property():
    """Hypothesis sweep: odd mer ladders in 3..31, ragged read lengths
    (incl. len < max mer), random walker buffers/activity, and random
    targets — all five output lanes bit-identical between backends."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    odd_k = st.sampled_from(range(3, 32, 2))

    @settings(max_examples=15, deadline=None)
    @given(
        ks=st.lists(odd_k, min_size=1, max_size=3, unique=True),
        E=st.integers(1, 12),
        capacity_pow=st.integers(4, 10),
        max_ext=st.integers(1, 24),
        with_target=st.booleans(),
        seed=st.integers(0, 2**31 - 1),
    )
    def inner(ks, E, capacity_pow, max_ext, with_target, seed):
        mer_sizes = tuple(sorted(ks))
        rng = np.random.default_rng(seed)
        wt, tag_bits = _random_tables(rng, mer_sizes, 1 << capacity_pow,
                                      num_reads=32)
        hi, lo, contig, active = _random_walkers(rng, E)
        kw = dict(mer_sizes=mer_sizes, tag_bits=tag_bits, max_ext=max_ext)
        if with_target:
            seed_len = int(rng.integers(3, min(31, max(mer_sizes)) + 1))
            tgt = rng.integers(0, 4, size=(E, seed_len)).astype(np.uint8)
            t_hi, t_lo = kmer.pack_window(jnp.asarray(tgt), k=seed_len)
            kw.update(target_hi=t_hi, target_lo=t_lo, seed_len=seed_len)
        want = _walk_both(wt, hi, lo, contig, active, **kw)
        # inactive walkers never move
        inact = ~np.asarray(active)
        assert (np.asarray(want.ext_len)[inact] == 0).all()
        assert (np.asarray(want.status)[inact] == local_assembly.DONE).all()
        assert (np.asarray(want.ext_len) <= max_ext).all()

    inner()


# ---------------------------------------------------------------------------
# pipeline-level parity (Local; Mesh(8) twin in test_mesh_backend_parity.py)
# ---------------------------------------------------------------------------


def _parity_fixture():
    # distinct seeds/sizes from tests/test_kernel_parity.py so the two
    # suites do not retread one fixture
    comm = mgsim.sample_community(71, num_genomes=3, genome_len=280,
                                  abundance_sigma=0.4)
    reads, _ = mgsim.generate_reads(72, comm, num_pairs=280, read_len=60,
                                    err_rate=0.004)
    return reads


def _assert_same_result(a, b):
    for key in ("scaffold_seqs", "contigs", "alive", "alignments"):
        for x, y in zip(jax.tree.leaves(a[key]), jax.tree.leaves(b[key])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=key)


def test_assemble_scaffolds_identical_across_backends():
    reads = _parity_fixture()
    plan = AssemblyPlan.from_dataset(reads, (17, 21, 4), unique_rate=0.2)
    out_p = Assembler(
        dataclasses.replace(plan, kernel_backend="pallas"), Local()
    ).assemble(reads)
    out_r = Assembler(
        dataclasses.replace(plan, kernel_backend="ref"), Local()
    ).assemble(reads)
    _assert_same_result(out_p, out_r)
    lens = np.asarray(out_p["scaffold_seqs"].lengths)
    assert int(lens.sum()) > 0
    # the walk stage must have actually run (extension accounted per round)
    assert any(s.extended_bases > 0 for s in out_p["stats"])


def test_assemble_stream_scaffolds_identical_across_backends():
    reads = _parity_fixture()
    plan = AssemblyPlan.from_dataset(reads, (17, 21, 4), unique_rate=0.2)
    batches = batches_from_readset(reads, 256)
    assert len(batches) >= 2
    out_p = Assembler(
        dataclasses.replace(plan, kernel_backend="pallas"), Local()
    ).assemble_stream(batches)
    out_r = Assembler(
        dataclasses.replace(plan, kernel_backend="ref"), Local()
    ).assemble_stream(batches)
    _assert_same_result(out_p, out_r)
