"""Alignment kernel backend parity: pallas and ref must be BIT-identical.

`ops.seed_probe` fuses the alignment front half (per-read seed
extraction + canonicalization + linear-probe against the seed index +
candidate vote) that `alignment.align_reads` previously ran as separate
jnp stages, and `ops.sw_extend` / `ops.dht_lookup` back the verify and
table paths (DESIGN.md §8).  These tests hold the dispatch layer to its
contract:

  * op-level: pallas and ref produce identical candidate (contig,
    cstart, orient) stacks over ragged read lengths (including reads
    shorter than the seed), seed lengths on both sides of the 16-base
    lane split, saturated 16-slot seed indexes, and read counts off the
    kernel tile grid (the ops padding path);
  * `ops.sw_extend` pads awkward batch sizes (B=1, B=block+1) to the
    kernel tile and trims, bit-identical to the ref on every lane;
  * `alignment.align_reads` — Hamming and gapped verify alike — returns
    bit-identical Alignments under both backends, and the REPRO_KERNELS
    env override is consulted on each new hot path;
  * pipeline-level parity (assemble / assemble_stream / Mesh(8)) rides
    the existing suites in tests/test_kernel_parity.py and
    tests/test_mesh_*.py, which now traverse these kernels.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import alignment
from repro.core.types import ContigSet, ReadSet
from repro.kernels import ops

CAND_LANES = ("contig", "cstart", "orient")


def _fixture(seed, *, C=4, clen=120, R=33, L=80, seed_len=21,
             capacity=1 << 12, n_frac=0.02, ragged=True):
    """Contigs + reads sampled from them (half reverse-complemented,
    N-sprinkled, ragged lengths incl. len < seed_len) + a seed index."""
    rng = np.random.default_rng(seed)
    genome = rng.integers(0, 4, size=(C, clen)).astype(np.uint8)
    contigs = ContigSet(
        bases=jnp.asarray(genome),
        lengths=jnp.full((C,), clen, jnp.int32),
        depths=jnp.ones((C,), jnp.float32),
    )
    alive = jnp.ones((C,), bool)
    bases = np.full((R, L), 4, np.uint8)
    for r in range(R):
        c = rng.integers(0, C)
        s = rng.integers(0, max(1, clen - L + 1))
        w = genome[c, s:s + L].copy()
        if rng.random() < 0.5:
            w = (3 - w)[::-1]  # reverse complement
        bases[r, : len(w)] = w
    bases[rng.random((R, L)) < n_frac] = 4
    if ragged:
        lengths = rng.integers(0, L + 1, size=(R,)).astype(np.int32)
    else:
        lengths = np.full((R,), L, np.int32)
    reads = ReadSet(
        bases=jnp.asarray(bases), lengths=jnp.asarray(lengths),
        mate=jnp.full((R,), -1, jnp.int32), insert_size=0,
    )
    index = alignment.build_seed_index(
        contigs, alive, seed_len=seed_len, capacity=capacity
    )
    return reads, contigs, index


def _probe_both(reads, index, *, seed_len, stride=16):
    positions = tuple(alignment._seed_positions(
        reads.max_len, seed_len, stride
    ))
    t = index.table
    args = (reads.bases, reads.lengths, t.slot_hi, t.slot_lo, t.used,
            t.max_probe, index.contig, index.pos, index.flip, index.multi)
    kw = dict(seed_len=seed_len, positions=positions)
    got = ops.seed_probe(*args, backend="pallas", **kw)
    want = ops.seed_probe(*args, backend="ref", **kw)
    for g, w, name in zip(got, want, CAND_LANES):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    return want


# ---------------------------------------------------------------------------
# op-level parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed_len", [15, 16, 17, 27])
def test_seed_probe_bit_identical_across_lane_split(seed_len):
    """Seed lengths straddling the 16-base hi/lo lane boundary."""
    reads, _, index = _fixture(seed_len * 13, seed_len=seed_len)
    want = _probe_both(reads, index, seed_len=seed_len)
    assert int((np.asarray(want[0])[:, 0] >= 0).sum()) > 0, \
        "fixture must actually place reads"


@pytest.mark.parametrize("R", [1, 7, 9])
def test_seed_probe_awkward_read_counts(R):
    """Row counts off the kernel tile grid go through the ops padding."""
    reads, _, index = _fixture(R * 31, R=R)
    want = _probe_both(reads, index, seed_len=21)
    assert np.asarray(want[0]).shape == (R, 2)


def test_seed_probe_saturated_index():
    """capacity=16 seed index: probe chains wrap, regions saturate, and
    most seeds collide into `multi` — candidates must still agree."""
    reads, _, index = _fixture(99, capacity=16)
    _probe_both(reads, index, seed_len=21)


def test_seed_probe_backend_parity_property():
    """Hypothesis sweep: seed lengths on both sides of the lane split,
    ragged reads (incl. len < seed_len), tiny/saturated capacities, and
    read counts across the tile boundary — all three candidate lanes
    bit-identical between backends."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(
        seed_len=st.sampled_from([11, 15, 16, 17, 21, 27]),
        R=st.integers(1, 12),
        extra=st.integers(0, 24),
        cap_pow=st.integers(4, 10),
        stride=st.integers(4, 24),
        seed=st.integers(0, 2**31 - 1),
    )
    def inner(seed_len, R, extra, cap_pow, stride, seed):
        reads, _, index = _fixture(
            seed, R=R, L=seed_len + extra, seed_len=seed_len,
            capacity=1 << cap_pow,
        )
        want = _probe_both(reads, index, seed_len=seed_len, stride=stride)
        # reads shorter than the seed can never receive a candidate
        short = np.asarray(reads.lengths) < seed_len
        assert (np.asarray(want[0])[short] == -1).all()

    inner()


# ---------------------------------------------------------------------------
# ops.sw_extend padding
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B", [1, 9])
def test_sw_extend_ops_pads_awkward_batches(B):
    """The kernel asserts B % block_b == 0; ops.sw_extend pads any B
    (here 1 and block+1) and trims — bit-identical to the ref, with a
    zero-length row mixed in to pin the padding mask."""
    rng = np.random.default_rng(B * 17)
    QL, TL = 24, 32
    q = rng.integers(0, 4, size=(B, QL)).astype(np.uint8)
    t = np.concatenate([q, rng.integers(0, 4, (B, TL - QL))], axis=1)
    t[rng.random((B, TL)) < 0.1] = rng.integers(0, 4)
    qlen = np.full((B,), QL, np.int32)
    tlen = np.full((B,), TL, np.int32)
    qlen[0] = 0  # empty row: must score 0, not pick up padding garbage
    args = (jnp.asarray(q), jnp.asarray(t), jnp.asarray(qlen),
            jnp.asarray(tlen))
    got = ops.sw_extend(*args, band=7, backend="pallas")
    want = ops.sw_extend(*args, band=7, backend="ref")
    for g, w, name in zip(got, want, ("score", "qend", "tend")):
        assert np.asarray(g).shape == (B,)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    assert int(np.asarray(got[0])[0]) == 0


# ---------------------------------------------------------------------------
# align_reads parity (Hamming and gapped verify)
# ---------------------------------------------------------------------------


def _align_both(reads, contigs, index, **kw):
    got = alignment.align_reads(reads, contigs, index, backend="pallas",
                                **kw)
    want = alignment.align_reads(reads, contigs, index, backend="ref",
                                 **kw)
    for name in ("contig", "cstart", "orient", "matches", "overlap"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name)),
            np.asarray(getattr(want, name)), err_msg=name,
        )
    return want


@pytest.mark.parametrize("gapped", [False, True])
def test_align_reads_bit_identical_across_backends(gapped):
    """Full align_reads — seed probe + (Hamming | sw_extend) verify —
    under both backends, on a fixture that actually places reads."""
    reads, contigs, index = _fixture(7, n_frac=0.01)
    want = _align_both(reads, contigs, index, seed_len=21, gapped=gapped)
    placed = np.asarray(want.contig)[:, 0] >= 0
    long_enough = np.asarray(reads.lengths) >= 42
    assert placed[long_enough].mean() > 0.5, \
        "fixture must place most full-length reads"


# ---------------------------------------------------------------------------
# dispatch rules on the new hot paths
# ---------------------------------------------------------------------------


def test_env_override_reaches_new_ops(monkeypatch):
    """REPRO_KERNELS is consulted by seed_probe, dht_lookup, and
    sw_extend themselves.  The backends are bit-identical, so equality
    cannot show the override took effect; a BOGUS value raising from
    inside each op can (mirrors tests/test_kernel_parity.py)."""
    reads, contigs, index = _fixture(3, R=8)
    t = index.table
    monkeypatch.setenv(ops.ENV_VAR, "not-a-backend")
    with pytest.raises(ValueError, match=ops.ENV_VAR):
        ops.seed_probe(
            reads.bases, reads.lengths, t.slot_hi, t.slot_lo, t.used,
            t.max_probe, index.contig, index.pos, index.flip, index.multi,
            seed_len=21, positions=(0,),
        )
    with pytest.raises(ValueError, match=ops.ENV_VAR):
        ops.dht_lookup(t.slot_hi, t.slot_lo, t.used, t.max_probe,
                       jnp.zeros((4,), jnp.uint32),
                       jnp.zeros((4,), jnp.uint32))
    with pytest.raises(ValueError, match=ops.ENV_VAR):
        z = jnp.zeros((2, 8), jnp.uint8)
        n = jnp.full((2,), 8, jnp.int32)
        ops.sw_extend(z, z, n, n)
    with pytest.raises(ValueError, match=ops.ENV_VAR):
        alignment.align_reads(reads, contigs, index, seed_len=21)
