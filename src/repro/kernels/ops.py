"""Kernel backend dispatch: the one entry point for every fused hot path.

Every compute hot-spot with a Pallas kernel is fronted here by a *backend*
choice (DESIGN.md §8):

  * ``"ref"``    — the pure-jnp oracle in `kernels.ref`, kept bit-identical
    (for integer kernels) or numerically validated (for float kernels).
    The default on every platform, TPU included.
  * ``"pallas"`` — the fused kernel.  On TPU it is handed to Mosaic, and
    none of the assembler kernels lowers for v5e yet (rank-1 8-row
    BlockSpecs, cumsum, 1-D table gathers, dynamic_slice): Mosaic's error
    propagates, nothing falls back.  On any other backend it runs in
    interpret mode (the kernel body still executes exactly, op for op),
    which is how the parity suites exercise it.

Selection order, strongest first:

  1. the ``REPRO_KERNELS`` environment variable (operator override — flips
     the whole process without touching plans or code);
  2. the explicit ``backend=`` argument (plumbed from
     ``AssemblyPlan.kernel_backend`` through the execution contexts);
  3. the default (`default_backend`): ``"ref"``.

The k-mer extraction path (`kmer_extract`) is THE system ingest hot path:
all extraction/canonicalization/hashing in core/, stream/, and dist/ goes
through this module — call `kernels.kmer_extract` nowhere else.  The
traversal twin is `mer_walk`: every §II-G contig-extension and §III-D
gap-closing ladder walk (Local, Mesh shard bodies, streaming driver)
dispatches here too.  The alignment hot path rounds out the set:
`seed_probe` (fused seed extraction + index probe + candidate vote,
§II-F), `sw_extend` (banded extension DP), and the `dht_insert` /
`dht_lookup` pair that backs `core.dht` — and through it every hash-table
build and probe in the system (§II-A).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from . import dht_probe as _dp
from . import flash_attention as _fa
from . import kmer_extract as _ke
from . import mer_walk as _mw
from . import ref
from . import seed_probe as _sp
from . import ssd_scan as _ssd
from . import sw_extend as _sw
from .dht_probe import BLOCK_QUERIES  # re-export  # noqa: F401
from .kmer_extract import BLOCK_READS, KmerLanes  # re-export  # noqa: F401
from .mer_walk import BLOCK_WALKERS, MerWalkOut  # re-export  # noqa: F401
from .sw_extend import BLOCK_B  # re-export  # noqa: F401

BACKENDS = ("pallas", "ref")
ENV_VAR = "REPRO_KERNELS"


def default_backend() -> str:
    """The default backend: the jnp ref, on every platform.

    A kernel returns to the default only once it lowers for the chip AND
    beats the ref in every benchmark cell that runs it; today none lowers
    for v5e (tests/test_v5e_compile.py compiles the ref path instead).
    Off-TPU the kernels only interpret.  Force `pallas` via REPRO_KERNELS
    or `AssemblyPlan.kernel_backend` to exercise the kernel path (the
    parity tests and the kernels bench do exactly that).
    """
    return "ref"


def resolve_backend(backend=None) -> str:
    """Resolve a kernel backend name: env override > explicit > default.

    The env var is read per call, but call sites that dispatch INSIDE a
    jitted stage (e.g. `alignment.align_reads`, where `backend` is a
    static argument) bake the resolved choice into the compiled program —
    set REPRO_KERNELS before the first run of a process, not between
    runs, if you want it to govern every stage.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        env = env.strip().lower()
        if env not in BACKENDS:
            raise ValueError(
                f"{ENV_VAR}={env!r} is not a kernel backend; valid: {BACKENDS}"
            )
        return env
    if backend is None:
        return default_backend()
    b = str(backend).lower()
    if b not in BACKENDS:
        raise ValueError(
            f"kernel backend {backend!r} unknown; valid: {BACKENDS} "
            f"(or None for the default)"
        )
    return b


def _interpret() -> bool:
    """Interpret Pallas kernels off-TPU; on TPU always compile natively."""
    return jax.default_backend() != "tpu"


def _legacy(use_kernel, backend):
    """Map the historical use_kernel flag onto the backend argument."""
    if use_kernel is None:
        return backend
    return "pallas" if use_kernel else "ref"


def kmer_extract(bases, lengths, *, k: int, backend=None,
                 use_kernel=None) -> KmerLanes:
    """Fused k-mer lanes for a dense [R, L] read batch (any R).

    The single extraction path of the system: canonical (hi, lo) codes,
    owner hash, canonicalized left/right extension bases, strand flip, and
    validity come from one kernel invocation per read tile.  Rows are
    padded to the kernel's BLOCK_READS tiling internally and trimmed back,
    so callers never see the tile constraint.
    """
    b = resolve_backend(_legacy(use_kernel, backend))
    if b == "ref":
        return ref.kmer_extract_ref(bases, lengths, k=k)
    R, L = bases.shape
    pad = (-R) % BLOCK_READS
    if pad:
        bases = jnp.concatenate(
            [bases, jnp.full((pad, L), 4, bases.dtype)]
        )
        lengths = jnp.concatenate(
            [lengths, jnp.zeros((pad,), lengths.dtype)]
        )
    lanes = _ke.kmer_extract(bases, lengths, k=k, interpret=_interpret())
    if pad:
        lanes = KmerLanes(*(x[:R] for x in lanes))
    return lanes


def mer_walk(
    wt,
    start_hi,
    start_lo,
    contig,
    active,
    *,
    mer_sizes: tuple,
    tag_bits: int,
    max_ext: int = 64,
    min_votes: int = 1,
    dominance: int = 4,
    target_hi=None,
    target_lo=None,
    seed_len: int = 0,
    backend=None,
) -> MerWalkOut:
    """Fused dynamic-mer ladder walk for E contig ends (§II-G / §III-D).

    The single walk path of the system: contig extension
    (`local_assembly.extend_with_tables`) and gap closing
    (`gap_closing.close_and_render_with_tables`) — on Local, Mesh, and the
    streaming driver — all land here.  `wt` is a
    `local_assembly.WalkTables`-shaped record (tuples of per-rung
    `dht.HashTable`s plus right/left extension histograms, one rung per
    entry of `mer_sizes`).  The ref walk indexes the per-rung tuples as
    they are; only the Pallas kernel gets them stacked into [n_rungs, ...]
    arrays — a copy of every table, several GB at pipeline widths.

    Pass `target_hi/lo` + `seed_len` > 0 for the gap-closing variant: a
    walker whose buffer suffix reaches the target seed records
    `hit_pos` (accepted-base count) and halts with status HIT.
    """
    b = resolve_backend(backend)
    n = len(mer_sizes)
    assert len(wt.tables) == n, (len(wt.tables), mer_sizes)
    cap = wt.tables[0].capacity
    assert all(t.capacity == cap for t in wt.tables), "rung capacity mismatch"
    tables = (
        tuple(t.slot_hi for t in wt.tables),
        tuple(t.slot_lo for t in wt.tables),
        tuple(t.used for t in wt.tables),
        tuple(jnp.asarray(t.max_probe, jnp.int32) for t in wt.tables),
        tuple(wt.right_hist),
        tuple(wt.left_hist),
    )
    has_target = target_hi is not None
    if has_target:
        assert seed_len > 0, "target walk needs seed_len > 0"
    else:
        seed_len = 0
        target_hi = jnp.zeros_like(start_hi)
        target_lo = jnp.zeros_like(start_lo)
    E = start_hi.shape[0]
    args = [start_hi, start_lo, jnp.asarray(contig, jnp.int32),
            jnp.asarray(active, bool), target_hi, target_lo]
    kw = dict(mer_sizes=tuple(mer_sizes), tag_bits=tag_bits, max_ext=max_ext,
              min_votes=min_votes, dominance=dominance, seed_len=seed_len)
    if b == "ref":
        return ref.mer_walk_ref(*args, *tables, **kw)
    pad = (-E) % BLOCK_WALKERS
    if pad:
        zeros = lambda x: jnp.zeros((pad,), x.dtype)
        args = [jnp.concatenate([x, zeros(x)]) for x in args]
    out = _mw.mer_walk(*args, *(jnp.stack(t) for t in tables),
                       interpret=_interpret(), **kw)
    if pad:
        out = MerWalkOut(*(x[:E] for x in out))
    return out


def kmer_hash(hi, lo):
    """Owner-routing hash of packed canonical codes.

    Backend-invariant by construction: per-occurrence hashes come out of
    the extraction kernel's `hash` lane; this jnp path exists for the
    table-row scale re-hash (owner routing of pre-combined count tables,
    DESIGN.md §8) where a kernel launch would cost more than the math.
    Both are the same murmur3-fmix construction, asserted equal in
    tests/test_kernel_parity.py.
    """
    from repro.core import kmer as _kmer

    return _kmer.kmer_hash(hi, lo)


def dht_lookup(slot_hi, slot_lo, used, max_probe, hi, lo, valid=None, *,
               backend=None):
    """Slot index per query key against an open-addressed table (§II-A).

    The single DHT probe path of the system: `core.dht.lookup` lands here
    (array-level interface so kernels stay leaf modules).  Queries of any
    shape are flattened, padded to the kernel's BLOCK_QUERIES tiling, and
    trimmed back; the table arrays ride one VMEM-resident copy per tile.
    """
    if valid is None:
        valid = jnp.ones(hi.shape, bool)
    if resolve_backend(backend) == "ref":
        return ref.dht_lookup_ref(slot_hi, slot_lo, used,
                                  jnp.asarray(max_probe, jnp.int32),
                                  hi, lo, valid)
    q = hi.shape
    fhi, flo = hi.reshape(-1), lo.reshape(-1)
    fval = valid.reshape(-1)
    N = fhi.shape[0]
    pad = (-N) % BLOCK_QUERIES
    if pad:
        fhi = jnp.concatenate([fhi, jnp.zeros((pad,), fhi.dtype)])
        flo = jnp.concatenate([flo, jnp.zeros((pad,), flo.dtype)])
        fval = jnp.concatenate([fval, jnp.zeros((pad,), bool)])
    out = _dp.dht_lookup(
        slot_hi, slot_lo, used,
        jnp.asarray(max_probe, jnp.int32).reshape(1),
        fhi, flo, fval, interpret=_interpret(),
    )
    if pad:
        out = out[:N]
    return out.reshape(q)


def dht_insert(slot_hi, slot_lo, used, max_probe, hi, lo, valid, *,
               backend=None):
    """Bulk-synchronous insert rounds for an open-addressed table (§II-A).

    `core.dht.insert` (and through it every table build: walk-table fold,
    seed index, de Bruijn index) lands here.  No key tiling on the pallas
    path — the claim rounds are a global race, so the whole batch and the
    table share one kernel instance (see kernels/dht_probe.py).
    Returns (slot_hi, slot_lo, used, max_probe, slots), max_probe scalar.
    """
    if resolve_backend(backend) == "ref":
        return ref.dht_insert_ref(slot_hi, slot_lo, used,
                                  jnp.asarray(max_probe, jnp.int32),
                                  hi, lo, valid)
    shi, slo, u, mp, slots = _dp.dht_insert(
        slot_hi, slot_lo, used,
        jnp.asarray(max_probe, jnp.int32).reshape(1),
        hi, lo, valid, interpret=_interpret(),
    )
    return shi, slo, u, mp[0], slots


def seed_probe(bases, lengths, slot_hi, slot_lo, used, max_probe,
               contig, pos, flip, multi, *, seed_len: int, positions,
               backend=None):
    """Fused alignment front half (§II-F): seeds -> voted top-2 placements.

    `alignment.align_reads` dispatches here: per-read seed extraction at
    the static stride positions, canonicalization, linear probe of the
    VMEM-resident seed index, candidate placement, and the O(S^2) vote +
    top-2 distinct-contig selection — one kernel pass per read tile.  Rows
    are padded to the BLOCK_READS tiling internally and trimmed back.
    Returns (contig, cstart, orient), each [R, 2] (-1 contig = unplaced).
    """
    positions = tuple(positions)
    if resolve_backend(backend) == "ref":
        return ref.seed_probe_ref(
            bases, lengths, slot_hi, slot_lo, used,
            jnp.asarray(max_probe, jnp.int32),
            contig, pos, flip, multi,
            seed_len=seed_len, positions=positions,
        )
    R, L = bases.shape
    pad = (-R) % _sp.BLOCK_READS
    if pad:
        bases = jnp.concatenate([bases, jnp.full((pad, L), 4, bases.dtype)])
        lengths = jnp.concatenate([lengths, jnp.zeros((pad,), lengths.dtype)])
    c, s, o = _sp.seed_probe(
        bases, lengths, slot_hi, slot_lo, used,
        jnp.asarray(max_probe, jnp.int32).reshape(1),
        contig, pos, flip, multi,
        seed_len=seed_len, positions=positions, interpret=_interpret(),
    )
    if pad:
        c, s, o = c[:R], s[:R], o[:R]
    return c, s, o


def sw_extend(query, target, qlen, tlen, *, band: int = 15, backend=None,
              use_kernel=None, **kw):
    """Banded SW extension scores (§II-F), rows padded to the kernel tile.

    Padded rows carry zero lengths and sentinel bases, so their scores are
    0 and get trimmed; callers never see the BLOCK_B constraint.
    """
    if resolve_backend(_legacy(use_kernel, backend)) == "pallas":
        B, QL = query.shape
        TL = target.shape[1]
        block_b = kw.pop("block_b", BLOCK_B)
        pad = (-B) % block_b
        if pad:
            query = jnp.concatenate(
                [query, jnp.full((pad, QL), 4, query.dtype)]
            )
            target = jnp.concatenate(
                [target, jnp.full((pad, TL), 4, target.dtype)]
            )
            qlen = jnp.concatenate([qlen, jnp.zeros((pad,), qlen.dtype)])
            tlen = jnp.concatenate([tlen, jnp.zeros((pad,), tlen.dtype)])
        out = _sw.sw_extend(query, target, qlen, tlen, band=band,
                            interpret=_interpret(), block_b=block_b, **kw)
        if pad:
            out = tuple(x[:B] for x in out)
        return out
    return ref.sw_extend_ref(query, target, qlen, tlen, band=band, **kw)


def flash_attention(q, k, v, *, causal: bool = True, backend=None,
                    use_kernel=None, **kw):
    if resolve_backend(_legacy(use_kernel, backend)) == "pallas":
        return _fa.flash_attention(q, k, v, causal=causal,
                                   interpret=_interpret(), **kw)
    return ref.flash_attention_ref(q, k, v, causal=causal)


def ssd_scan(x, a, b, c, *, chunk: int = 128, backend=None, use_kernel=None):
    if resolve_backend(_legacy(use_kernel, backend)) == "pallas":
        return _ssd.ssd_scan(x, a, b, c, chunk=chunk, interpret=_interpret())
    return ref.ssd_scan_ref(x, a, b, c)
