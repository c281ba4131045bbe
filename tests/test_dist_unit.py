"""Single-device unit tests for repro.dist (the 8-device paths live in
test_mesh_*.py; everything here runs on the default one-device env)."""
import numpy as np
import jax.numpy as jnp

from repro.core import kmer_analysis
from repro.core.types import INVALID_BASE, KmerSet
from repro.data import mgsim
from repro.dist import capacity as cap_lib
from repro.dist import pipeline as dist


def test_shard_reads_pads_to_even_split():
    _, reads, _ = mgsim.single_genome_reads(1, genome_len=300, coverage=10)
    R = reads.num_reads
    S = 8
    assert R % S != 0, "fixture should exercise the padding path"
    sh = dist.shard_reads(reads, S)
    r_pad = -(-R // S) * S
    assert sh.num_reads == r_pad
    assert sh.max_len == reads.max_len
    # mask marks exactly the original rows, in order
    v = np.asarray(sh.valid)
    assert v[:R].all() and not v[R:].any()
    np.testing.assert_array_equal(np.asarray(sh.bases)[:R],
                                  np.asarray(reads.bases))
    np.testing.assert_array_equal(np.asarray(sh.lengths)[:R],
                                  np.asarray(reads.lengths))
    # padding rows are inert: zero length, all-invalid bases, no mate
    assert (np.asarray(sh.lengths)[R:] == 0).all()
    assert (np.asarray(sh.bases)[R:] == INVALID_BASE).all()
    assert (np.asarray(sh.mate)[R:] == -1).all()


def test_shard_reads_even_split_is_unpadded():
    _, reads, _ = mgsim.single_genome_reads(2, genome_len=300, coverage=10)
    S = 2
    assert reads.num_reads % S == 0
    sh = dist.shard_reads(reads, S)
    assert sh.num_reads == reads.num_reads
    assert np.asarray(sh.valid).all()


def _kset_from_counts(hi, lo, count, capacity):
    n = len(hi)
    pad = capacity - n
    z = lambda x, fill, dt: jnp.asarray(
        np.concatenate([np.asarray(x), np.full((pad,), fill)]).astype(dt)
    )
    return KmerSet(
        hi=z(hi, 0xFFFFFFFF, np.uint32),
        lo=z(lo, 0, np.uint32),
        count=z(count, 0, np.int32),
        left_cnt=jnp.zeros((capacity, 4), jnp.int32),
        right_cnt=jnp.zeros((capacity, 4), jnp.int32),
        left_ext=jnp.zeros((capacity,), jnp.uint8),
        right_ext=jnp.zeros((capacity,), jnp.uint8),
        used=z(count, 0, np.int32) > 0,
    )


def test_gather_ksets_reports_overflow():
    # 12 distinct keys into an 8-slot gather: must FLAG, not silently drop
    keys = np.arange(12, dtype=np.uint32)
    kset = _kset_from_counts(
        hi=np.zeros(12, np.uint32), lo=keys,
        count=np.full(12, 3, np.int32), capacity=16,
    )
    merged = dist.gather_ksets(kset, capacity=8)
    assert bool(merged["overflow"])
    assert int(merged["n_unique"]) == 12
    # roomy gather: nothing lost, keys ascending, counts intact
    ok = dist.gather_ksets(kset, capacity=16)
    assert not bool(ok["overflow"])
    live = np.asarray(ok["count"]) > 0
    assert live.sum() == 12
    np.testing.assert_array_equal(np.asarray(ok["lo"])[live], keys)
    assert (np.asarray(ok["count"])[live] == 3).all()


def test_distributed_kmer_analysis_single_shard_oracle():
    # S=1 runs on the default device and must equal the single-shard path
    _, reads, _ = mgsim.single_genome_reads(3, genome_len=300, coverage=15)
    mesh = dist.data_mesh(1)
    kset, route_ovf, tab_ovf = dist.distributed_kmer_analysis(
        reads, mesh, k=21, pre_capacity=1 << 12, capacity=1 << 12
    )
    assert int(route_ovf) == 0 and int(tab_ovf) == 0
    merged = dist.gather_ksets(kset, capacity=1 << 12)
    ref = kmer_analysis.analyze(reads, k=21, capacity=1 << 12, min_count=2)
    ru = np.asarray(ref.used)
    got = np.asarray(merged["count"]) >= 2
    np.testing.assert_array_equal(np.asarray(merged["hi"])[got],
                                  np.asarray(ref.hi)[ru])
    np.testing.assert_array_equal(np.asarray(merged["lo"])[got],
                                  np.asarray(ref.lo)[ru])
    np.testing.assert_array_equal(np.asarray(merged["count"])[got],
                                  np.asarray(ref.count)[ru])


def test_route_capacity_heuristic_bounds():
    assert cap_lib.default_route_capacity(4096, 8) == 1024
    # never exceeds what one sender can hold
    assert cap_lib.default_route_capacity(64, 1) == 64
    assert cap_lib.default_route_capacity(1, 64) == 1


def test_plan_kmer_budget_shapes():
    b = cap_lib.plan_kmer_budget(1000, 60, 21, 8)
    assert b.pre_capacity & (b.pre_capacity - 1) == 0
    assert 1 <= b.route_capacity <= b.pre_capacity
    assert b.recv_rows() == 8 * b.route_capacity
    assert b.bytes_per_shard() > 0


def test_sharded_kmer_analysis_contig_injection_single_shard_oracle():
    """§II-H on the mesh path: contig k-mers enter the owner exchange as
    pseudo-counted partials; with S=1 the result must equal the Local
    extract -> merge -> finalize sequence exactly."""
    from repro.api import extract_contig_kmers
    from repro.core import pipeline as pipe  # noqa: F401  (shim import path)
    from repro.core.types import ContigSet
    from repro.dist import stages

    genome, reads, _ = mgsim.single_genome_reads(4, genome_len=300,
                                                 coverage=15)
    # a fake "previous iteration" contig set: the genome itself + a dead row
    C, L = 4, 512
    bases = np.full((C, L), 4, np.uint8)
    bases[0, :300] = np.asarray(genome)
    contigs = ContigSet(
        bases=jnp.asarray(bases),
        lengths=jnp.asarray([300, 0, 0, 0], jnp.int32),
        depths=jnp.ones((C,), jnp.float32),
    )
    alive = jnp.asarray([True, False, False, False])

    mesh = dist.data_mesh(1)
    kset, route_ovf, tab_ovf = stages.sharded_kmer_analysis(
        dist.shard_reads(reads, 1), mesh, k=21,
        pre_capacity=1 << 12, capacity=1 << 12,
        prev_contigs=(contigs, alive), contig_weight=4,
    )
    assert int(route_ovf) == 0 and int(tab_ovf) == 0

    # Local oracle: count reads, merge pseudo-counted contig table, finalize
    hi, lo, left, right, valid = kmer_analysis.occurrences(reads, k=21)
    tab = kmer_analysis.count_occurrences(hi, lo, left, right, valid,
                                          capacity=1 << 12)
    ctab = extract_contig_kmers(contigs, alive, k=21, capacity=1 << 12,
                                weight=4)
    merged = kmer_analysis.merge_counts(tab, ctab, capacity=1 << 12)
    ref = kmer_analysis.finalize(merged, min_count=2,
                                 policy=kmer_analysis.ExtensionPolicy())

    got_used = np.asarray(kset.used)
    ref_used = np.asarray(ref.used)
    assert got_used.sum() == ref_used.sum()
    np.testing.assert_array_equal(np.asarray(kset.hi)[got_used],
                                  np.asarray(ref.hi)[ref_used])
    np.testing.assert_array_equal(np.asarray(kset.count)[got_used],
                                  np.asarray(ref.count)[ref_used])
    np.testing.assert_array_equal(np.asarray(kset.left_ext)[got_used],
                                  np.asarray(ref.left_ext)[ref_used])


def test_merge_contig_kmers_equals_merge_of_pseudo_table():
    """Local's one-fold §II-H merge (contig occurrences as weighted rows)
    gives the table that counting the contigs apart and merging gives."""
    from repro.core.types import ContigSet

    genome, reads, _ = mgsim.single_genome_reads(5, genome_len=300,
                                                 coverage=15)
    bases = np.full((4, 512), 4, np.uint8)
    bases[0, :300] = np.asarray(genome)
    bases[2, :150] = np.asarray(genome)[75:225]   # a repeated stretch
    contigs = ContigSet(jnp.asarray(bases),
                        jnp.asarray([300, 0, 150, 0], jnp.int32),
                        jnp.ones((4,), jnp.float32))
    tab = kmer_analysis.count_occurrences(
        *kmer_analysis.occurrences(reads, k=21), capacity=1 << 12
    )
    want = kmer_analysis.merge_counts(
        tab, kmer_analysis.pseudo_count_table(
            contigs.bases, contigs.lengths, k=21, capacity=1 << 12, weight=4),
        capacity=1 << 12,
    )
    got = kmer_analysis.merge_contig_kmers(
        tab, contigs.bases, contigs.lengths, k=21, capacity=1 << 12, weight=4
    )
    for key in ("hi", "lo", "count", "left_cnt", "right_cnt", "n_unique",
                "overflow"):
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]), err_msg=key)
