"""Kernel backend parity: pallas and ref must be BIT-identical (DESIGN.md §8).

The fused Pallas extraction kernel serves every k-mer hot path in the
system (core k-mer analysis, streaming Bloom ingest, alignment seeding,
walk tables, distributed owner routing).  These tests hold the dispatch
layer to its contract:

  * lane-level: property test over odd k in 3..31 and ragged read lengths
    (including reads shorter than k) — canonical codes, extensions, owner
    hashes, strand flips, and validity identical between backends;
  * pipeline-level: `assemble` and `assemble_stream` on Local produce
    bit-identical scaffolds under both backends.  (The Mesh(8) twin lives
    in tests/test_mesh_backend_parity.py; combined with the existing
    mesh-vs-local and stream-vs-memory parity tests, every context/path
    pair is pinned.)
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.api import Assembler, AssemblyPlan, Local
from repro.api.plan import PlanError
from repro.data import mgsim
from repro.kernels import ops
from repro.stream.batches import batches_from_readset

LANES = ("hi", "lo", "hash", "left", "right", "flip", "valid")


def _assert_lanes_equal(got, want):
    wv = np.asarray(want.valid)
    np.testing.assert_array_equal(np.asarray(got.valid), wv)
    for field in LANES[:-1]:
        gi, wi = np.asarray(getattr(got, field)), np.asarray(getattr(want, field))
        np.testing.assert_array_equal(gi[wv], wi[wv], err_msg=field)


def _random_reads(rng, R, L, k):
    bases = rng.integers(0, 4, size=(R, L)).astype(np.uint8)
    bases[rng.random((R, L)) < 0.03] = 4  # N sprinkle
    # ragged lengths INCLUDING reads shorter than k (zero valid windows)
    lengths = rng.integers(0, L + 1, size=(R,)).astype(np.int32)
    return jnp.asarray(bases), jnp.asarray(lengths)


# ---------------------------------------------------------------------------
# lane-level parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [3, 31])
@pytest.mark.parametrize("R", [1, 7, 13])  # not divisible by BLOCK_READS
def test_backends_bit_identical_awkward_shapes(k, R):
    """Row counts off the kernel tile grid go through the ops padding."""
    rng = np.random.default_rng(R * 37 + k)
    L = k + 9
    bases, lengths = _random_reads(rng, R, L, k)
    got = ops.kmer_extract(bases, lengths, k=k, backend="pallas")
    want = ops.kmer_extract(bases, lengths, k=k, backend="ref")
    assert got.hi.shape == (R, L)
    _assert_lanes_equal(got, want)


def test_backend_parity_property():
    """Hypothesis sweep: odd k in 3..31, ragged lengths incl. len < k.

    Asserts identical canonical (hi, lo), canonicalized extensions, owner
    hashes, strand flips, and validity masks between the pallas kernel and
    the jnp ref — plus that the kernel's hash lane and the table-row-scale
    `ops.kmer_hash` (the Local and Mesh owner-routing hash) agree, so
    owner assignment cannot depend on which path computed it.
    """
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(
        k=st.sampled_from(range(3, 32, 2)),
        R=st.integers(1, 12),
        extra=st.integers(0, 16),
        seed=st.integers(0, 2**31 - 1),
    )
    def inner(k, R, extra, seed):
        rng = np.random.default_rng(seed)
        L = k + extra
        bases, lengths = _random_reads(rng, R, L, k)
        got = ops.kmer_extract(bases, lengths, k=k, backend="pallas")
        want = ops.kmer_extract(bases, lengths, k=k, backend="ref")
        _assert_lanes_equal(got, want)
        # reads shorter than k must contribute zero valid windows
        W = L - k + 1
        v = np.asarray(want.valid)[:, :W]
        short = np.asarray(lengths) < k
        assert not v[short].any()
        # owner hash: kernel lane == table-scale re-hash of the same codes
        wv = np.asarray(want.valid)
        h2 = np.asarray(ops.kmer_hash(got.hi, got.lo))
        np.testing.assert_array_equal(np.asarray(got.hash)[wv], h2[wv])

    inner()


# ---------------------------------------------------------------------------
# dispatch rules
# ---------------------------------------------------------------------------


def test_env_override_wins(monkeypatch):
    monkeypatch.setenv(ops.ENV_VAR, "ref")
    assert ops.resolve_backend("pallas") == "ref"
    monkeypatch.delenv(ops.ENV_VAR)
    assert ops.resolve_backend("pallas") == "pallas"
    # the default is the jnp ref on every platform: no Pallas kernel
    # lowers for TPU v5e yet
    assert ops.resolve_backend(None) == ops.default_backend() == "ref"


def test_unknown_backend_rejected(monkeypatch):
    with pytest.raises(ValueError, match="valid"):
        ops.resolve_backend("cuda")
    monkeypatch.setenv(ops.ENV_VAR, "tpu-fast")
    with pytest.raises(ValueError, match=ops.ENV_VAR):
        ops.resolve_backend(None)


def test_plan_validates_kernel_backend():
    with pytest.raises(PlanError, match="kernel_backend"):
        AssemblyPlan(kernel_backend="vulkan")
    assert AssemblyPlan(kernel_backend="ref").kernel_backend == "ref"


# ---------------------------------------------------------------------------
# pipeline-level parity (Local; Mesh(8) twin in test_mesh_backend_parity.py)
# ---------------------------------------------------------------------------


def _parity_fixture():
    comm = mgsim.sample_community(41, num_genomes=2, genome_len=300,
                                  abundance_sigma=0.3)
    reads, _ = mgsim.generate_reads(42, comm, num_pairs=300, read_len=60,
                                    err_rate=0.003)
    return reads


def _assert_same_result(a, b):
    for key in ("scaffold_seqs", "contigs", "alive", "alignments"):
        for x, y in zip(jax.tree.leaves(a[key]), jax.tree.leaves(b[key])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=key)


def test_assemble_scaffolds_identical_across_backends():
    reads = _parity_fixture()
    plan = AssemblyPlan.from_dataset(reads, (17, 21, 4), unique_rate=0.2)
    import dataclasses

    out_p = Assembler(
        dataclasses.replace(plan, kernel_backend="pallas"), Local()
    ).assemble(reads)
    out_r = Assembler(
        dataclasses.replace(plan, kernel_backend="ref"), Local()
    ).assemble(reads)
    _assert_same_result(out_p, out_r)
    lens = np.asarray(out_p["scaffold_seqs"].lengths)
    assert int(lens.sum()) > 0  # parity of real assemblies, not of nothing


def test_assemble_stream_scaffolds_identical_across_backends():
    reads = _parity_fixture()
    plan = AssemblyPlan.from_dataset(reads, (17, 21, 4), unique_rate=0.2)
    import dataclasses

    batches = batches_from_readset(reads, 256)
    assert len(batches) >= 2
    out_p = Assembler(
        dataclasses.replace(plan, kernel_backend="pallas"), Local()
    ).assemble_stream(batches)
    out_r = Assembler(
        dataclasses.replace(plan, kernel_backend="ref"), Local()
    ).assemble_stream(batches)
    _assert_same_result(out_p, out_r)


def test_env_override_reaches_the_pipeline(monkeypatch):
    """REPRO_KERNELS is consulted on the hot path itself.

    The two backends are bit-identical, so an equality check could not
    tell whether the override took effect; a BOGUS value raising from
    inside the k-mer stage can."""
    reads = _parity_fixture()
    plan = AssemblyPlan.from_dataset(
        reads, (21, 21, 4), unique_rate=0.2, kernel_backend="pallas"
    )
    monkeypatch.setenv(ops.ENV_VAR, "not-a-backend")
    with pytest.raises(ValueError, match=ops.ENV_VAR):
        Assembler(plan, Local()).contig_rounds(reads)
