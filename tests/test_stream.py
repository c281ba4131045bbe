"""repro.stream: out-of-core ingest, two-pass Bloom admission, parity.

The acceptance bar for the streaming subsystem (ISSUE 3):
  * `assemble_stream` over >= 2 batches reproduces the in-memory path's
    scaffolds (here: bit-identically, on Local — the Mesh(8) twin lives
    in tests/test_mesh_vs_local.py);
  * `AssemblyPlan.from_stream(...).bytes()` does not grow with total
    read count;
  * the two-pass Bloom admission drops >= 90% of singleton-error k-mers
    on a simulated error profile.
"""
import tempfile

import numpy as np
import jax
import pytest

from repro.api import Assembler, AssemblyPlan, Local, PlanError
from repro.core import kmer_analysis
from repro.data import mgsim
from repro.stream import (
    BatchSource,
    batches_from_readset,
    streaming_kmer_analysis,
)


# ---------------------------------------------------------------------------
# batch sources
# ---------------------------------------------------------------------------


def test_batches_from_readset_shapes_and_mates():
    comm = mgsim.sample_community(11, num_genomes=2, genome_len=300)
    reads, _ = mgsim.generate_reads(12, comm, num_pairs=100, read_len=50)
    batches = batches_from_readset(reads, 64)
    assert len(batches) == -(-200 // 64)
    for b in batches:
        assert b.bases.shape == (64, 50)
    # every batch pairs its mates locally: mate[mate[i]] == i
    for b in batches:
        m = np.asarray(b.mate)
        paired = m >= 0
        assert (m[m[paired]] == np.arange(64)[paired]).all()
    # last batch padding is inert
    lens = np.asarray(batches[-1].lengths)
    assert (lens[200 - 64 * 3:] == 0).all()
    # concatenated bases reproduce the original order
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(b.bases) for b in batches])[:200],
        np.asarray(reads.bases),
    )


def test_batches_from_readset_rejects_odd_batch():
    _, reads, _ = mgsim.single_genome_reads(7, genome_len=150, coverage=4)
    with pytest.raises(ValueError, match="even"):
        batches_from_readset(reads, 63)


def test_mgsim_generate_read_batches_fixed_shape():
    comm = mgsim.sample_community(13, num_genomes=2, genome_len=300)
    src = BatchSource(lambda: mgsim.generate_read_batches(
        14, comm, 70, pairs_per_batch=32, read_len=50))
    batches = list(src)
    assert len(batches) == 3
    assert all(b.bases.shape == (64, 50) for b in batches)
    # deterministic re-iteration (pass 2 must see the same bytes)
    again = list(src)
    for a, b in zip(batches, again):
        np.testing.assert_array_equal(np.asarray(a.bases), np.asarray(b.bases))
    # final batch padded: 70 - 64 = 6 pairs -> 12 live rows
    assert int((np.asarray(batches[-1].lengths) > 0).sum()) == 12


# ---------------------------------------------------------------------------
# plan sizing: memory bill independent of dataset size
# ---------------------------------------------------------------------------


def test_from_stream_bytes_independent_of_total_reads():
    small = AssemblyPlan.from_stream(2048, 60, (17, 21, 4),
                                     total_reads=10_000)
    huge = AssemblyPlan.from_stream(2048, 60, (17, 21, 4),
                                    total_reads=7_500_000_000)
    assert small == huge  # total_reads must not touch ANY field
    assert small.bytes() == huge.bytes()
    # while batch size is a real dial...
    bigger_batch = AssemblyPlan.from_stream(8192, 60, (17, 21, 4))
    assert bigger_batch.bytes() > small.bytes()
    # ...and the Bloom budget prices in
    roomy = AssemblyPlan.from_stream(2048, 60, (17, 21, 4),
                                     bloom_bits=1 << 24)
    assert roomy.stage_bytes()["bloom_filters"] == 2 << 24
    assert roomy.bytes() > small.bytes()


def test_from_stream_validation():
    with pytest.raises(PlanError, match="batch_reads"):
        AssemblyPlan.from_stream(101, 60)
    with pytest.raises(PlanError, match="bloom_bits"):
        AssemblyPlan.from_stream(100, 60, bloom_bits=1000)


def test_from_stream_unique_kmers_overrides_batch_heuristic():
    by_batch = AssemblyPlan.from_stream(4096, 60)
    by_census = AssemblyPlan.from_stream(4096, 60, unique_kmers=1 << 20)
    assert by_census.kmer_capacity > by_batch.kmer_capacity


# ---------------------------------------------------------------------------
# two-pass streamed k-mer analysis
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def erroneous_reads():
    genome, reads, _ = mgsim.single_genome_reads(
        51, genome_len=600, coverage=25, err_rate=0.01
    )
    return reads


def test_streamed_counts_match_in_memory_oracle(erroneous_reads):
    reads = erroneous_reads
    batches = batches_from_readset(reads, 64)
    assert len(batches) >= 3
    run, stats = streaming_kmer_analysis(
        batches, k=21, capacity=1 << 14, bloom_bits=1 << 17
    )
    kset = kmer_analysis.finalize(
        run, min_count=2, policy=kmer_analysis.ExtensionPolicy()
    )
    ref = kmer_analysis.analyze(reads, k=21, capacity=1 << 14, min_count=2)
    ru, gu = np.asarray(ref.used), np.asarray(kset.used)
    assert ru.sum() == gu.sum()
    np.testing.assert_array_equal(np.asarray(ref.hi)[ru],
                                  np.asarray(kset.hi)[gu])
    np.testing.assert_array_equal(np.asarray(ref.count)[ru],
                                  np.asarray(kset.count)[gu])
    np.testing.assert_array_equal(np.asarray(ref.left_ext)[ru],
                                  np.asarray(kset.left_ext)[gu])
    assert stats.batches_pass1 == stats.batches_pass2 == len(batches)
    assert stats.table_overflow == 0


def test_two_pass_admission_drops_90pct_of_error_singletons(erroneous_reads):
    """Acceptance: the error-singleton mass never reaches table capacity."""
    reads = erroneous_reads
    batches = batches_from_readset(reads, 64)
    run, stats = streaming_kmer_analysis(
        batches, k=21, capacity=1 << 14, bloom_bits=1 << 17
    )
    exact = kmer_analysis.count_occurrences(
        *kmer_analysis.occurrences(reads, k=21), capacity=1 << 15
    )
    counts = np.asarray(exact["count"])
    n_singletons = int((counts == 1).sum())
    n_true = int((counts >= 2).sum())
    admitted_keys = int((np.asarray(run["count"]) > 0).sum())
    singletons_admitted = admitted_keys - n_true
    assert n_singletons > 500, "error profile should mint many singletons"
    drop_rate = 1.0 - singletons_admitted / n_singletons
    assert drop_rate >= 0.90, (drop_rate, singletons_admitted, n_singletons)
    # admission also shows up in occurrence units
    assert stats.occurrences_admitted < stats.occurrences_total


def test_streamed_admission_independent_of_batch_split(erroneous_reads):
    """The two-sighting rule is a per-key property: a key split across
    batches (one sighting each) must still be admitted."""
    reads = erroneous_reads
    runs = []
    for batch_reads in (64, 250):  # 250 = one batch holding everything
        run, _ = streaming_kmer_analysis(
            batches_from_readset(reads, batch_reads),
            k=21, capacity=1 << 14, bloom_bits=1 << 17,
        )
        runs.append(run)
    a, b = runs
    av, bv = np.asarray(a["count"]) > 0, np.asarray(b["count"]) > 0
    np.testing.assert_array_equal(np.asarray(a["hi"])[av],
                                  np.asarray(b["hi"])[bv])
    np.testing.assert_array_equal(np.asarray(a["count"])[av],
                                  np.asarray(b["count"])[bv])


def test_streaming_checkpoint_resume(erroneous_reads):
    reads = erroneous_reads
    batches = batches_from_readset(reads, 64)
    kw = dict(k=21, capacity=1 << 13, bloom_bits=1 << 16)
    with tempfile.TemporaryDirectory() as d:
        cold, s_cold = streaming_kmer_analysis(
            batches, checkpoint_dir=d, **kw
        )
        assert not s_cold.resumed
        assert s_cold.batches_pass2 == len(batches)
        # a rerun restores the final batch-boundary state.  Poisoning every
        # batch after the first (the fingerprint probe) proves the resumed
        # run SKIPS processing: the table can only be identical if no
        # poisoned batch was ever analyzed.  Counters restore with the
        # state, so stats still describe the whole logical run.
        poisoned = [batches[0]] + [
            dataclasses_replace_bases(b) for b in batches[1:]
        ]
        warm, s_warm = streaming_kmer_analysis(
            poisoned, checkpoint_dir=d, **kw
        )
        assert s_warm.resumed
        assert s_warm.batches_pass2 == s_cold.batches_pass2
        for key in ("hi", "lo", "count", "left_cnt", "right_cnt"):
            np.testing.assert_array_equal(np.asarray(cold[key]),
                                          np.asarray(warm[key]))


def dataclasses_replace_bases(batch):
    """A batch of the same shape whose content would change the counts."""
    return batch._replace(bases=(batch.bases + 1) % 4)


def test_streaming_checkpoint_rejects_different_dataset(erroneous_reads):
    """A stale checkpoint dir must not silently serve another run's table."""
    reads = erroneous_reads
    batches = batches_from_readset(reads, 64)
    kw = dict(k=21, capacity=1 << 13, bloom_bits=1 << 16)
    with tempfile.TemporaryDirectory() as d:
        streaming_kmer_analysis(batches, checkpoint_dir=d, **kw)
        other = [dataclasses_replace_bases(b) for b in batches]
        with pytest.raises(ValueError, match="fingerprint"):
            streaming_kmer_analysis(other, checkpoint_dir=d, **kw)


def test_single_shot_iterator_rejected(erroneous_reads):
    batches = batches_from_readset(erroneous_reads, 64)
    with pytest.raises(TypeError, match="single-shot"):
        streaming_kmer_analysis(
            iter(batches), k=21, capacity=1 << 13, bloom_bits=1 << 16
        )
    from repro.api import Assembler, AssemblyPlan, Local

    plan = AssemblyPlan.from_stream(64, 60, (21, 21, 4))
    with pytest.raises(TypeError, match="BatchSource"):
        Assembler(plan, Local()).assemble_stream(iter(batches))


# ---------------------------------------------------------------------------
# full streamed pipeline parity (Local; the Mesh twin is a distributed test)
# ---------------------------------------------------------------------------


def test_assemble_stream_matches_in_memory_scaffolds():
    comm = mgsim.sample_community(5, num_genomes=3, genome_len=300,
                                  abundance_sigma=0.3)
    reads, _ = mgsim.generate_reads(6, comm, num_pairs=400, read_len=60,
                                    err_rate=0.003)
    plan = AssemblyPlan.from_dataset(reads, (17, 21, 4), unique_rate=0.2)
    out_mem = Assembler(plan, Local()).assemble(reads)
    batches = batches_from_readset(reads, 256)
    assert len(batches) >= 2
    out_st = Assembler(plan, Local()).assemble_stream(batches)
    for a, b in zip(jax.tree.leaves(out_mem["scaffold_seqs"]),
                    jax.tree.leaves(out_st["scaffold_seqs"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(out_mem["alive"]),
                                  np.asarray(out_st["alive"]))
    assert all(v == 0 for v in out_st["overflow"].values()), out_st["overflow"]
    # per-k streaming accounting rode along
    assert set(out_st["stream_stats"]) == set(plan.ks())
    for st in out_st["stream_stats"].values():
        assert st.batches_pass2 == len(batches)


def test_assemble_stream_rejects_min_count_below_two():
    """The two-sighting rule drops singletons by construction; min_count=1
    would silently diverge from the in-memory path, so it must refuse."""
    _, reads, _ = mgsim.single_genome_reads(7, genome_len=150, coverage=4)
    plan = AssemblyPlan.from_stream(64, 60, (21, 21, 4), min_count=1)
    with pytest.raises(PlanError, match="min_count >= 2"):
        Assembler(plan, Local()).assemble_stream(
            batches_from_readset(reads, 64))


def test_assemble_stream_plan_from_stream_end_to_end():
    """from_stream-sized plan drives the whole streamed pipeline."""
    comm = mgsim.sample_community(21, num_genomes=2, genome_len=300,
                                  abundance_sigma=0.3)
    reads, _ = mgsim.generate_reads(22, comm, num_pairs=300, read_len=60,
                                    err_rate=0.003)
    plan = AssemblyPlan.from_stream(
        200, 60, (21, 21, 4), unique_kmers=800, slack=4.0,
    )
    batches = batches_from_readset(reads, 200)
    out = Assembler(plan, Local()).assemble_stream(batches)
    lens = np.asarray(out["scaffold_seqs"].lengths)
    assert int(lens.sum()) > 300  # it actually assembles something
    assert out["overflow"]["kmer_table"] == 0
