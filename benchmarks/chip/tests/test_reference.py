"""The reference's k-mer codes: up to k = 32 one word a k-mer, the code it
always was, and for any k right against a plain string statement of
canonical k-mers."""
import numpy as np
import pytest

from lib import community, reference

ACGT = "ACGT"


def codes_up_to_32(rows, lengths, k):
    """The one-word codes as the reference packed them before it took
    longer k-mers: 2 bits a base, first base highest, the lesser of a
    k-mer and its reverse complement."""
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


def random_rows(seed, R=40, L=150, n_rate=0.01):
    """Bases with scattered N (4) and rows cut short."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 4, size=(R, L)).astype(np.uint8)
    rows[rng.random((R, L)) < n_rate] = 4
    lengths = rng.integers(L // 2, L + 1, size=R).astype(np.int32)
    lengths[0] = L
    return rows, lengths


def text(row, n):
    return "".join("ACGTN"[int(b)] for b in row[:n])


def revcomp(s):
    return s[::-1].translate(str.maketrans("ACGT", "TGCA"))


def canonical_strings(rows, lengths, k):
    """Each window inside a row's length with no N, row by row, as the
    lesser string of the window and its reverse complement (A < C < G < T,
    the order of the codes)."""
    out = []
    for row, n in zip(rows, lengths):
        s = text(row, n)
        for i in range(len(s) - k + 1):
            w = s[i:i + k]
            if "N" not in w:
                out.append(min(w, revcomp(w)))
    return out


def decode(codes, k):
    """Rows of 32-base words, first base highest, back to strings."""
    out = []
    for row in codes:
        s = ""
        for w, word in enumerate(row):
            width = min(32, k - 32 * w)
            s += "".join(ACGT[(int(word) >> (2 * (width - 1 - q))) & 3]
                         for q in range(width))
        out.append(s)
    return out


@pytest.mark.parametrize("k", [5, 17, 21, 31, 32])
def test_codes_up_to_32_are_unchanged(k):
    rows, lengths = random_rows(k)
    got = reference.canonical_kmers(rows, lengths, k)
    want = codes_up_to_32(rows, lengths, k)
    assert got.dtype == np.uint64 and got.shape == (len(want), 1)
    assert np.array_equal(got[:, 0], want)
    assert decode(got, k) == canonical_strings(rows, lengths, k)


@pytest.mark.parametrize("k", [33, 55, 77, 99])
def test_longer_kmers_match_plain_strings(k):
    rows, lengths = random_rows(k)
    # a reverse-complement palindrome at the start of a row
    half = rows[1, :k // 2] & 3
    rows[1, :k // 2 * 2] = np.concatenate([half, 3 - half[::-1]])
    lengths[1] = rows.shape[1]
    got = reference.canonical_kmers(rows, lengths, k)
    assert got.dtype == np.uint64 and got.shape[1] == -(-k // 32)
    want = canonical_strings(rows, lengths, k)
    assert len(want) > 200
    assert decode(got, k) == want


@pytest.mark.parametrize("k", [33, 64, 65])
def test_longer_kmers_of_a_strand_and_its_reverse_complement_agree(k):
    rows, lengths = random_rows(k, R=8, n_rate=0.0)
    lengths[:] = rows.shape[1]
    rc = (3 - rows[:, ::-1]).astype(np.uint8)
    keys = reference.kmer_keys(rows, lengths, k)
    keys_rc = reference.kmer_keys(rc, lengths, k)
    assert np.array_equal(np.unique(keys), np.unique(keys_rc))
    assert len(np.unique(keys)) == len(set(canonical_strings(rows, lengths,
                                                             k)))


def tiny_assembly(cell, ks):
    """A sample of the tiny cell and an assembly of its genomes' pieces,
    with one random contig among them whose k-mers are not solid."""
    s = community.sample(2**31 + 3, 1, pairs=cell["traffic"][
        "pairs_per_sample"], community=cell["config"]["community"],
        reads=cell["config"]["reads"])
    rng = np.random.default_rng(5)
    pieces = [s.genomes[0][200:2600], s.genomes[1][:1500],
              rng.integers(0, 4, 900).astype(np.uint8),
              s.genomes[1][1600:2100]]
    rounds = []
    for r, k in enumerate(ks):
        width = max(len(p) for p in pieces)
        bases = np.full((len(pieces) + 1, width), 4, np.uint8)
        for i, p in enumerate(pieces):
            bases[i, :len(p)] = p
        lengths = np.array([len(p) for p in pieces] + [0], np.int32)
        alive = np.array([True, True, True, r == 0, False])
        rounds.append({"k": k, "n_kmers": 1000 * (r + 1), "bases": bases,
                       "lengths": lengths, "alive": alive})
    reads = {"bases": s.bases, "lengths": s.lengths}
    return {"rounds": rounds}, reads


def counts_by_strings(asm, reads, g):
    """`kmer_counts` stated over sets of canonical strings."""
    count_diff, unsolid, prev = 0, 0, None
    for rnd in asm["rounds"]:
        k = rnd["k"]
        seen = {}
        for w in canonical_strings(reads["bases"], reads["lengths"], k):
            seen[w] = seen.get(w, 0) + 1
        solid = {w for w, c in seen.items() if c >= g["min_count"]}
        if prev is not None:
            live = prev["alive"] & (prev["lengths"] > 0)
            solid |= set(canonical_strings(prev["bases"][live],
                                           prev["lengths"][live], k))
        count_diff += abs(rnd["n_kmers"] - len(solid))
        e = g["max_ext"]
        live = rnd["alive"] & (rnd["lengths"] > 0)
        for row, n in zip(rnd["bases"][live], rnd["lengths"][live]):
            inner = np.full_like(row, 4)
            inner[e:n - e] = row[e:n - e]
            unsolid += sum(w not in solid
                           for w in canonical_strings([inner], [n], k))
        prev = rnd
    return count_diff, unsolid


def test_kmer_counts_of_the_tiny_cell_read_as_before(tiny_cell,
                                                     monkeypatch):
    g = tiny_cell["config"]["guarantees"]
    asm, reads = tiny_assembly(tiny_cell, (17, 21))
    now = reference.kmer_counts(asm, reads, g)
    monkeypatch.setattr(reference, "canonical_kmers",
                        lambda *a: codes_up_to_32(*a)[:, None])
    before = reference.kmer_counts(asm, reads, g)
    assert now == before
    assert now[1] > 0


@pytest.mark.parametrize("ks", [(17, 21), (33, 55), (21, 77)])
def test_kmer_counts_match_sets_of_strings(tiny_cell, ks):
    g = tiny_cell["config"]["guarantees"]
    asm, reads = tiny_assembly(tiny_cell, ks)
    got = reference.kmer_counts(asm, reads, g)
    assert got == counts_by_strings(asm, reads, g)
    assert got[1] > 0
