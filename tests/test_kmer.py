"""Property + oracle tests for the dual-lane k-mer codec."""
import numpy as np
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import kmer
from repro.core.types import INVALID_BASE

BASES = "ACGT"
COMP = {"A": "T", "C": "G", "G": "C", "T": "A"}


def py_pack(s: str) -> int:
    v = 0
    for ch in s:
        v = (v << 2) | BASES.index(ch)
    return v


def py_rc(s: str) -> str:
    return "".join(COMP[c] for c in reversed(s))


def split64(v: int):
    return np.uint32(v >> 32), np.uint32(v & 0xFFFFFFFF)


def dna(draw, k):
    return "".join(draw(st.sampled_from(BASES)) for _ in range(k))


@st.composite
def kmer_strategy(draw):
    k = draw(st.integers(min_value=2, max_value=31))
    return k, dna(draw, k)


@settings(max_examples=60, deadline=None)
@given(kmer_strategy())
def test_pack_matches_python_oracle(data):
    k, s = data
    bases = jnp.array([[BASES.index(c) for c in s]], dtype=jnp.uint8)
    hi, lo = kmer.pack_window(bases, k=k)
    ehi, elo = split64(py_pack(s))
    assert int(hi[0]) == int(ehi) and int(lo[0]) == int(elo)


@settings(max_examples=60, deadline=None)
@given(kmer_strategy())
def test_decode_roundtrip(data):
    k, s = data
    bases = jnp.array([BASES.index(c) for c in s], dtype=jnp.uint8)
    hi, lo = kmer.pack_window(bases[None], k=k)
    out = kmer.decode(hi, lo, k=k)[0]
    assert np.array_equal(np.asarray(out), np.asarray(bases))


@settings(max_examples=60, deadline=None)
@given(kmer_strategy())
def test_rc_matches_oracle_and_is_involution(data):
    k, s = data
    bases = jnp.array([[BASES.index(c) for c in s]], dtype=jnp.uint8)
    hi, lo = kmer.pack_window(bases, k=k)
    rhi, rlo = kmer.reverse_complement(hi, lo, k=k)
    ehi, elo = split64(py_pack(py_rc(s)))
    assert int(rhi[0]) == int(ehi) and int(rlo[0]) == int(elo)
    hhi, llo = kmer.reverse_complement(rhi, rlo, k=k)
    assert int(hhi[0]) == int(hi[0]) and int(llo[0]) == int(lo[0])


@settings(max_examples=60, deadline=None)
@given(kmer_strategy())
def test_canonical_invariant_under_rc(data):
    k, s = data
    bases = jnp.array([[BASES.index(c) for c in s]], dtype=jnp.uint8)
    hi, lo = kmer.pack_window(bases, k=k)
    rhi, rlo = kmer.reverse_complement(hi, lo, k=k)
    c1 = kmer.canonical(hi, lo, k=k)
    c2 = kmer.canonical(rhi, rlo, k=k)
    assert int(c1[0][0]) == int(c2[0][0]) and int(c1[1][0]) == int(c2[1][0])
    # canonical is the lexicographic min of the two packings
    expect = min(py_pack(s), py_pack(py_rc(s)))
    assert (int(c1[0][0]) << 32) | int(c1[1][0]) == expect


@settings(max_examples=40, deadline=None)
@given(kmer_strategy(), st.integers(min_value=0, max_value=3))
def test_append_prepend(data, b):
    k, s = data
    bases = jnp.array([[BASES.index(c) for c in s]], dtype=jnp.uint8)
    hi, lo = kmer.pack_window(bases, k=k)
    nb = jnp.array([b], dtype=jnp.uint8)
    ahi, alo = kmer.append_base(hi, lo, nb, k=k)
    expect = py_pack(s[1:] + BASES[b])
    assert (int(ahi[0]) << 32) | int(alo[0]) == expect
    phi, plo = kmer.prepend_base(hi, lo, nb, k=k)
    expect = py_pack(BASES[b] + s[:-1])
    assert (int(phi[0]) << 32) | int(plo[0]) == expect


def test_extract_kmers_dense():
    # two reads, one with an N and one short
    s0 = "ACGTACGTAC"
    s1 = "ACGNACGT"
    L = 12
    k = 4

    def enc(s):
        v = [("ACGTN".index(c)) for c in s] + [4] * (L - len(s))
        return v

    bases = jnp.array([enc(s0), enc(s1)], dtype=jnp.uint8)
    lengths = jnp.array([len(s0), len(s1)], dtype=jnp.int32)
    hi, lo, valid, left, right = kmer.extract_kmers(bases, lengths, k=k)
    W = L - k + 1
    assert hi.shape == (2, W)
    # read 0: windows 0..6 valid
    v0 = np.asarray(valid[0])
    assert v0[: len(s0) - k + 1].all() and not v0[len(s0) - k + 1 :].any()
    # read 1: windows containing the N (positions 0..3) invalid
    v1 = np.asarray(valid[1])
    expect1 = [False, False, False, False, True]
    assert list(v1[: len(s1) - k + 1]) == expect1
    # check packed value of first window of read 0 == ACGT
    assert (int(hi[0, 0]) << 32) | int(lo[0, 0]) == py_pack("ACGT")
    # extensions
    assert int(left[0, 0]) == INVALID_BASE  # no base before position 0
    assert int(right[0, 0]) == BASES.index(s0[k])
    assert int(left[0, 1]) == BASES.index(s0[0])
    # last valid window of read 0 has no right extension
    assert int(right[0, len(s0) - k]) == INVALID_BASE


@settings(max_examples=30, deadline=None)
@given(kmer_strategy())
def test_hash_deterministic_and_spread(data):
    k, s = data
    bases = jnp.array([[BASES.index(c) for c in s]], dtype=jnp.uint8)
    hi, lo = kmer.pack_window(bases, k=k)
    h1 = kmer.kmer_hash(hi, lo)
    h2 = kmer.kmer_hash(hi, lo)
    assert int(h1[0]) == int(h2[0])


def test_first_last_base():
    s = "GATTACAGATTACAGAT"  # k=17 crosses the 32-bit lane boundary
    k = len(s)
    bases = jnp.array([[BASES.index(c) for c in s]], dtype=jnp.uint8)
    hi, lo = kmer.pack_window(bases, k=k)
    assert int(kmer.first_base(hi, lo, k=k)[0]) == BASES.index("G")
    assert int(kmer.last_base(hi, lo, k=k)[0]) == BASES.index("T")


@pytest.mark.parametrize("width", [150, 128, 1])
def test_flat_lanes_pads_rows_to_the_lane_tiling(width):
    """Rows are padded to a multiple of LANE_TILE with the fill value, then
    flattened row-major; a row already on the tiling is not padded."""
    x = jnp.arange(3 * width, dtype=jnp.int32).reshape(3, width)
    out = np.asarray(kmer.flat_lanes(x, -1))
    padded = -(-width // kmer.LANE_TILE) * kmer.LANE_TILE
    assert out.shape == (3 * padded,)
    rows = out.reshape(3, padded)
    np.testing.assert_array_equal(rows[:, :width], np.asarray(x))
    assert (rows[:, width:] == -1).all()
