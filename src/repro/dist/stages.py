"""Distributed pipeline stages beyond k-mer analysis (DESIGN.md §6).

`dist.pipeline` established the paper's three distributed mechanisms for
ONE stage (k-mer analysis).  This module extends them to the whole
pipeline so `Assembler(plan, Mesh(S)).assemble(reads)` runs Algorithm 1 +
Algorithm 3 end to end on a mesh:

  * `sharded_kmer_analysis` — Alg. 2 owner exchange, now also carrying the
    previous iteration's *contig* k-mers (§II-H): each shard extracts and
    pre-combines pseudo-counted k-mers from its block of contig rows and
    routes them to the same hash owners as the read k-mers, so the merged
    per-owner table is globally correct before finalize.
  * `sharded_align` — each shard aligns its read block against the
    replicated contig set + seed index (contig state is orders of
    magnitude smaller than read state; replicating it is the TPU analogue
    of merAligner's software cache, with zero misses).
  * `sharded_extend` — §II-G local assembly after read localization: reads
    route to the shard owning their (mate-projected) aligned contig, each
    shard mer-walks only the contig ends it owns (c mod S) — the walk
    itself runs through the fused `kernels.ops.mer_walk` backend dispatch,
    same as Local (DESIGN.md §8) — and the extended rows combine by
    ownership.
  * `sharded_link_candidates` — post-localization per-shard scaffolding:
    read pairs route *atomically* to the owner of their first aligned
    contig with their alignments as payload, mate pointers are rebuilt
    from carried global indices, and splint/span witnesses are generated
    per shard; only the contig-scale link store and matching replicate.

Every stage reports overflow; nothing is silently dropped (§3.4).

Each stage is one jitted program per static configuration (mesh, sizes,
kernel backend): its `shard_map` is built inside the jit, so a later call
with the same shapes reuses the compiled program.  The backend is
resolved before the jit, as on the Local path, so REPRO_KERNELS still
takes effect once a program is cached.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import alignment, bloom, exchange, kmer_analysis, \
    local_assembly
from repro.core.kmer_analysis import ExtensionPolicy
from repro.core.scaffolding import candidate_links
from repro.core.types import ContigSet, INVALID_BASE, ReadSet
from repro.kernels import ops
from . import capacity as cap_lib
from .pipeline import AXIS, ShardedReads, kmer_owner, mesh_shards


def _pad_rows(x, rows: int, fill):
    pad = rows - x.shape[0]
    if pad <= 0:
        return x
    return jnp.concatenate(
        [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)]
    )


# ---------------------------------------------------------------------------
# k-mer analysis with contig-kmer owner exchange (§II-A + §II-H)
# ---------------------------------------------------------------------------


def sharded_kmer_analysis(
    reads,
    mesh,
    *,
    k: int,
    pre_capacity: int,
    capacity: int,
    route_capacity: Optional[int] = None,
    min_count: int = 2,
    policy: ExtensionPolicy = ExtensionPolicy(),
    prev_contigs=None,
    contig_weight: int = 4,
    backend=None,
):
    """Alg. 2 with optional §II-H contig-kmer injection.

    Args:
      reads: ShardedReads (or any ReadSet whose rows divide the mesh).
      prev_contigs: optional (ContigSet, alive) from the previous
        iteration; its k-mers enter the exchange as pseudo-counted
        partials weighted by `contig_weight`.
    Returns (kset, route_overflow, table_overflow) exactly like
    `dist.pipeline.distributed_kmer_analysis`.
    """
    return _sharded_kmer_analysis(
        reads.bases, reads.lengths, prev_contigs, mesh=mesh, k=k,
        pre_capacity=pre_capacity, capacity=capacity,
        route_capacity=route_capacity, min_count=min_count, policy=policy,
        contig_weight=contig_weight, backend=ops.resolve_backend(backend),
    )


@functools.partial(jax.jit, static_argnames=(
    "mesh", "k", "pre_capacity", "capacity", "route_capacity", "min_count",
    "policy", "contig_weight", "backend"))
def _sharded_kmer_analysis(bases, lengths, prev_contigs, *, mesh, k: int,
                           pre_capacity: int, capacity: int,
                           route_capacity: Optional[int], min_count: int,
                           policy: ExtensionPolicy, contig_weight: int,
                           backend: str):
    S = mesh_shards(mesh)
    has_contigs = prev_contigs is not None
    if route_capacity is None:
        # contig-carrying rounds route TWO pre-combined tables per sender
        # (read stream + §II-H pseudo-count stream), so the lanes must be
        # sized for the doubled worst-case holdings
        route_capacity = cap_lib.default_route_capacity(
            (2 if has_contigs else 1) * pre_capacity, S
        )
    assert bases.shape[0] % S == 0, (
        f"reads rows {bases.shape[0]} not divisible by {S}; "
        f"use shard_reads"
    )
    contig_args = ()
    if has_contigs:
        contigs, calive = prev_contigs
        C = contigs.capacity
        c_pad = -(-C // S) * S
        contig_args = (
            _pad_rows(contigs.bases, c_pad, INVALID_BASE),
            _pad_rows(jnp.where(calive, contigs.lengths, 0), c_pad, 0),
        )

    def body(bases, lengths, *contig_block):
        local = ReadSet(
            bases=bases, lengths=lengths,
            mate=jnp.full(lengths.shape, -1, jnp.int32), insert_size=0,
        )
        hi, lo, left, right, valid = kmer_analysis.occurrences(
            local, k=k, backend=backend
        )
        pre = kmer_analysis.count_occurrences(
            hi, lo, left, right, valid, capacity=pre_capacity
        )
        streams = [pre]
        local_ovf = pre["overflow"].astype(jnp.int32)
        if has_contigs:
            cb, cl = contig_block
            ctab = kmer_analysis.pseudo_count_table(
                cb, cl, k=k, capacity=pre_capacity, weight=contig_weight,
                backend=backend,
            )
            streams.append(ctab)
            local_ovf = local_ovf + ctab["overflow"].astype(jnp.int32)
        cat = lambda key: jnp.concatenate([s[key] for s in streams])
        phi, plo = cat("hi"), cat("lo")
        pcnt, plcnt, prcnt = cat("count"), cat("left_cnt"), cat("right_cnt")
        pvalid = pcnt != 0
        dest = kmer_owner(phi, plo, S)
        res = exchange.route(
            dest,
            (phi, plo, pcnt, plcnt, prcnt),
            pvalid,
            num_shards=S,
            capacity=route_capacity,
            axis_name=AXIS,
        )
        rhi, rlo, rcnt, rl, rr = res.payload
        tab = kmer_analysis.aggregate_weighted(
            rhi, rlo, rcnt, rl, rr, res.valid, capacity=capacity
        )
        kset = kmer_analysis.finalize(tab, min_count=min_count, policy=policy)
        table_ovf = jax.lax.psum(
            local_ovf + tab["overflow"].astype(jnp.int32), AXIS
        )
        return kset, res.overflow, table_ovf

    in_specs = (P(AXIS), P(AXIS)) + (P(AXIS),) * len(contig_args)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(AXIS), P(), P()),
        check_vma=False,
    )
    return fn(bases, lengths, *contig_args)


# ---------------------------------------------------------------------------
# streaming ingest: sharded Bloom pass + running owner-partitioned fold
# (paper §II-A/§II-B out-of-core; DESIGN.md §7)
# ---------------------------------------------------------------------------


def sharded_bloom_observe(
    batch,
    mesh,
    f1_bits,
    f2_bits,
    *,
    k: int,
    pre_capacity: int,
    route_capacity: Optional[int] = None,
    num_hashes: int = 3,
    backend=None,
):
    """Pass 1 of the streamed two-sighting rule for ONE batch.

    The Bloom filters are owner-partitioned ([S, bloom_bits]; shard s
    holds the bits of keys it owns): each shard pre-combines its block of
    the batch, routes (key, count) entries to their hash owners, and the
    owner — after an exact cross-sender aggregate — marks keys already in
    its f1 shard (sighted in an EARLIER batch) or arriving with batch
    count >= 2 in f2, then inserts everything into f1.  Ownership is
    total, so the two-sighting decision is globally exact per key — no
    false negatives, same as the single-device `bloom_observe`.

    Returns (f1_bits, f2_bits, route_overflow, table_overflow).
    """
    return _sharded_bloom_observe(
        batch, f1_bits, f2_bits, mesh=mesh, k=k, pre_capacity=pre_capacity,
        route_capacity=route_capacity, num_hashes=num_hashes,
        backend=ops.resolve_backend(backend),
    )


@functools.partial(jax.jit, static_argnames=(
    "mesh", "k", "pre_capacity", "route_capacity", "num_hashes", "backend"))
def _sharded_bloom_observe(batch, f1_bits, f2_bits, *, mesh, k: int,
                           pre_capacity: int, route_capacity: Optional[int],
                           num_hashes: int, backend: str):
    S = mesh_shards(mesh)
    from .pipeline import shard_reads

    reads = shard_reads(batch, S)
    if route_capacity is None:
        route_capacity = cap_lib.default_route_capacity(pre_capacity, S)
    recv_cap = S * route_capacity

    def body(bases, lengths, f1b, f2b):
        local = ReadSet(
            bases=bases, lengths=lengths,
            mate=jnp.full(lengths.shape, -1, jnp.int32), insert_size=0,
        )
        hi, lo, left, right, valid = kmer_analysis.occurrences(
            local, k=k, backend=backend
        )
        pre = kmer_analysis.count_occurrences(
            hi, lo, left, right, valid, capacity=pre_capacity
        )
        pvalid = pre["count"] > 0
        dest = kmer_owner(pre["hi"], pre["lo"], S)
        res = exchange.route(
            dest, (pre["hi"], pre["lo"], pre["count"]), pvalid,
            num_shards=S, capacity=route_capacity, axis_name=AXIS,
        )
        rhi, rlo, rcnt = res.payload
        # exact cross-sender dedupe: a key split over senders arrives as
        # several rows; summing them makes "count >= 2 within this batch"
        # a per-key truth before it touches the (lossy) filter
        zeros4 = jnp.zeros((rhi.shape[0], 4), jnp.int32)
        agg = kmer_analysis.aggregate_weighted(
            rhi, rlo, rcnt, zeros4, zeros4, res.valid, capacity=recv_cap
        )
        keys_ok = agg["count"] > 0
        f1 = bloom.BloomFilter(bits=f1b[0], num_hashes=num_hashes)
        f2 = bloom.BloomFilter(bits=f2b[0], num_hashes=num_hashes)
        seen = bloom.query(f1, agg["hi"], agg["lo"]) | (agg["count"] >= 2)
        f2 = bloom.insert(f2, agg["hi"], agg["lo"], keys_ok & seen)
        f1 = bloom.insert(f1, agg["hi"], agg["lo"], keys_ok)
        table_ovf = jax.lax.psum(pre["overflow"].astype(jnp.int32), AXIS)
        return f1.bits[None], f2.bits[None], res.overflow, table_ovf

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(), P()),
        check_vma=False,
    )
    return fn(reads.bases, reads.lengths, f1_bits, f2_bits)


def sharded_stream_fold(
    batch,
    mesh,
    f2_bits,
    run: dict,
    *,
    k: int,
    capacity: int,
    pre_capacity: int,
    route_capacity: Optional[int] = None,
    num_hashes: int = 3,
    backend=None,
):
    """Pass 2 for ONE batch: admit at the owner, fold into the running table.

    Each shard pre-combines its block (counts + extension histograms) and
    routes entries to their hash owners; the owner admits only keys its f2
    shard has seen twice and segment-reduces the admitted partials INTO its
    slice of the persistent running table (`aggregate_weighted` over the
    concatenation — the associative owner fold).  The running table is the
    flat [S * capacity] owner layout of `sharded_kmer_analysis`, so after
    the last batch it gathers/finalizes exactly like the in-memory path.

    Returns (run', (occ_total, occ_admitted), route_overflow,
    table_overflow).
    """
    return _sharded_stream_fold(
        batch, f2_bits, run, mesh=mesh, k=k, capacity=capacity,
        pre_capacity=pre_capacity, route_capacity=route_capacity,
        num_hashes=num_hashes, backend=ops.resolve_backend(backend),
    )


@functools.partial(jax.jit, static_argnames=(
    "mesh", "k", "capacity", "pre_capacity", "route_capacity", "num_hashes",
    "backend"))
def _sharded_stream_fold(batch, f2_bits, run, *, mesh, k: int, capacity: int,
                         pre_capacity: int, route_capacity: Optional[int],
                         num_hashes: int, backend: str):
    S = mesh_shards(mesh)
    from .pipeline import shard_reads

    reads = shard_reads(batch, S)
    if route_capacity is None:
        route_capacity = cap_lib.default_route_capacity(pre_capacity, S)

    def body(bases, lengths, f2b, run_hi, run_lo, run_cnt, run_l, run_r):
        local = ReadSet(
            bases=bases, lengths=lengths,
            mate=jnp.full(lengths.shape, -1, jnp.int32), insert_size=0,
        )
        hi, lo, left, right, valid = kmer_analysis.occurrences(
            local, k=k, backend=backend
        )
        pre = kmer_analysis.count_occurrences(
            hi, lo, left, right, valid, capacity=pre_capacity
        )
        pvalid = pre["count"] > 0
        dest = kmer_owner(pre["hi"], pre["lo"], S)
        res = exchange.route(
            dest,
            (pre["hi"], pre["lo"], pre["count"], pre["left_cnt"],
             pre["right_cnt"]),
            pvalid, num_shards=S, capacity=route_capacity, axis_name=AXIS,
        )
        rhi, rlo, rcnt, rl, rr = res.payload
        f2 = bloom.BloomFilter(bits=f2b[0], num_hashes=num_hashes)
        admitted = res.valid & bloom.query(f2, rhi, rlo)
        occ_total = jax.lax.psum(
            jnp.where(pvalid, pre["count"], 0).sum(), AXIS
        )
        occ_admitted = jax.lax.psum(jnp.where(admitted, rcnt, 0).sum(), AXIS)
        new = kmer_analysis.aggregate_weighted(
            jnp.concatenate([run_hi, rhi]),
            jnp.concatenate([run_lo, rlo]),
            jnp.concatenate([run_cnt, rcnt]),
            jnp.concatenate([run_l, rl]),
            jnp.concatenate([run_r, rr]),
            jnp.concatenate([run_cnt > 0, admitted]),
            capacity=capacity,
        )
        table_ovf = jax.lax.psum(
            pre["overflow"].astype(jnp.int32)
            + new["overflow"].astype(jnp.int32), AXIS
        )
        return (new["hi"], new["lo"], new["count"], new["left_cnt"],
                new["right_cnt"], occ_total, occ_admitted, res.overflow,
                table_ovf)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS),) * 8,
        out_specs=(P(AXIS),) * 5 + (P(),) * 4,
        check_vma=False,
    )
    out = fn(reads.bases, reads.lengths, f2_bits,
             run["hi"], run["lo"], run["count"], run["left_cnt"],
             run["right_cnt"])
    new_run = dict(zip(("hi", "lo", "count", "left_cnt", "right_cnt"), out[:5]))
    occ_total, occ_admitted, route_ovf, table_ovf = out[5:]
    return new_run, (occ_total, occ_admitted), route_ovf, table_ovf


# ---------------------------------------------------------------------------
# per-shard alignment against replicated contigs
# ---------------------------------------------------------------------------


def sharded_align(
    sharded: ShardedReads,
    contigs: ContigSet,
    sidx: alignment.SeedIndex,
    mesh,
    *,
    seed_len: int,
    stride: int = 16,
    gapped: bool = False,
    backend=None,
):
    """Align every read to the live contigs, one shard per read block.

    The contig set and seed index are replicated (P() specs): per-shard
    seed lookups are local by construction — the degenerate, zero-miss
    form of merAligner's remote-bucket cache.  Output arrays are in the
    global sharded layout, usable directly as full [R, 2] alignments.
    """
    return _sharded_align(
        sharded, contigs, sidx, mesh=mesh,
        insert_size=int(sharded.insert_size), seed_len=seed_len,
        stride=stride, gapped=gapped, backend=ops.resolve_backend(backend),
    )


@functools.partial(jax.jit, static_argnames=(
    "mesh", "insert_size", "seed_len", "stride", "gapped", "backend"))
def _sharded_align(sharded, contigs, sidx, *, mesh, insert_size: int,
                   seed_len: int, stride: int, gapped: bool, backend: str):
    S = mesh_shards(mesh)
    assert sharded.num_reads % S == 0
    table = sidx.table

    def body(bases, lengths, slot_hi, slot_lo, used, max_probe,
             s_contig, s_pos, s_flip, s_multi, cbases, clens, cdepths):
        local = ReadSet(
            bases=bases, lengths=lengths,
            mate=jnp.full(lengths.shape, -1, jnp.int32),
            insert_size=insert_size,
        )
        idx = alignment.SeedIndex(
            table=table.__class__(slot_hi, slot_lo, used, max_probe),
            contig=s_contig, pos=s_pos, flip=s_flip, multi=s_multi,
            seed_len=seed_len,
        )
        reps = ContigSet(bases=cbases, lengths=clens, depths=cdepths)
        return alignment.align_reads(
            local, reps, idx, seed_len=seed_len, stride=stride,
            gapped=gapped, backend=backend,
        )

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS)) + (P(),) * 11,
        out_specs=P(AXIS),
        check_vma=False,
    )
    return fn(
        sharded.bases, sharded.lengths,
        table.slot_hi, table.slot_lo, table.used, table.max_probe,
        sidx.contig, sidx.pos, sidx.flip, sidx.multi,
        contigs.bases, contigs.lengths, contigs.depths,
    )


# ---------------------------------------------------------------------------
# read localization carrying payload (§II-I generalized)
# ---------------------------------------------------------------------------


def localize_with(
    sharded: ShardedReads,
    dest_contig,
    payload: tuple,
    mesh,
    *,
    out_factor: int = 2,
):
    """Fig. 3 localization that carries per-read payload to the new shard.

    Each read routes to the shard owning `dest_contig[r]` (c mod S; rows
    with dest < 0 stay home), along with `payload` columns (alignment
    rows, global indices, ...).  Returns (localized ShardedReads,
    routed payload tuple, overflow) — overflow counts reads cut at either
    the route lanes or the receiver block, reported per §3.4.
    """
    rb, rl, rv, routed_pl, overflow = _localize_with(
        sharded.bases, sharded.lengths, sharded.valid, dest_contig,
        tuple(payload), mesh=mesh, out_factor=out_factor,
    )
    localized = ShardedReads(
        bases=rb,
        lengths=rl,
        mate=jnp.full(rl.shape, -1, jnp.int32),
        insert_size=sharded.insert_size,
        valid=rv,
    )
    return localized, routed_pl, overflow


@functools.partial(jax.jit, static_argnames=("mesh", "out_factor"))
def _localize_with(bases, lengths, valid, dest_contig, payload, *, mesh,
                   out_factor: int):
    S = mesh_shards(mesh)
    R = bases.shape[0]
    assert R % S == 0
    per = R // S
    out_per = out_factor * per
    route_cap = min(per, -(-2 * out_per // S))
    dest_contig = jnp.asarray(dest_contig, jnp.int32)[:R]

    def body(bases, lengths, valid, dc, *pl):
        me = jax.lax.axis_index(AXIS)
        dest = jnp.where(dc >= 0, dc % S, me).astype(jnp.int32)
        res = exchange.route(
            dest, (bases, lengths) + pl, valid,
            num_shards=S, capacity=route_cap, axis_name=AXIS,
        )
        routed, rv, ovf = exchange.compact(
            res.payload, res.valid, capacity=out_per
        )
        rb, rl = routed[0], routed[1]
        rb = jnp.where(rv[:, None], rb, jnp.uint8(INVALID_BASE))
        total_ovf = res.overflow + jax.lax.psum(ovf, AXIS)
        return (rb, rl) + tuple(routed[2:]) + (rv, total_ovf)

    n_pl = len(payload)
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS),) * (4 + n_pl),
        out_specs=(P(AXIS),) * (3 + n_pl) + (P(),),
        check_vma=False,
    )
    out = fn(bases, lengths, valid, dest_contig, *payload)
    rb, rl = out[0], out[1]
    routed_pl = out[2:2 + n_pl]
    rv, overflow = out[2 + n_pl], out[3 + n_pl]
    return rb, rl, rv, routed_pl, overflow


# ---------------------------------------------------------------------------
# per-shard local assembly of owned contigs (§II-G)
# ---------------------------------------------------------------------------


def sharded_extend(
    sharded: ShardedReads,
    contigs: ContigSet,
    alive,
    al,
    mesh,
    *,
    mer_sizes: tuple,
    capacity: int,
    max_ext: int = 64,
    out_factor: int = 2,
    backend=None,
):
    """Localize reads to their contig's owner, mer-walk owned contig ends.

    Contig c is owned by shard c mod S.  A read's effective contig is its
    own best hit, else its mate's (the §II-G mate projection — computed
    globally BEFORE localization so mate evidence survives the move).
    Each shard builds (contig, mer) walk tables from its localized read
    block only and extends only the contig rows it owns; the extended
    rows then combine by ownership.  Returns (ContigSet, overflow).
    """
    return _sharded_extend(
        sharded, contigs, alive, al, mesh=mesh,
        insert_size=int(sharded.insert_size), mer_sizes=tuple(mer_sizes),
        capacity=capacity, max_ext=max_ext, out_factor=out_factor,
        backend=ops.resolve_backend(backend),
    )


@functools.partial(jax.jit, static_argnames=(
    "mesh", "insert_size", "mer_sizes", "capacity", "max_ext", "out_factor",
    "backend"))
def _sharded_extend(sharded, contigs, alive, al, *, mesh, insert_size: int,
                    mer_sizes: tuple, capacity: int, max_ext: int,
                    out_factor: int, backend: str):
    S = mesh_shards(mesh)
    C = contigs.capacity
    R = sharded.num_reads
    aln0 = jnp.asarray(al.contig[:, 0], jnp.int32)[:R]
    # mate projection on the ORIGINAL layout (global mate indices)
    global_reads = ReadSet(
        bases=sharded.bases, lengths=sharded.lengths, mate=sharded.mate,
        insert_size=sharded.insert_size,
    )
    eff = local_assembly.localize_reads(global_reads, aln0)
    localized, (eff_loc,), overflow = localize_with(
        sharded, eff, (eff,), mesh, out_factor=out_factor
    )

    def body(bases, lengths, eff_c, cbases, clens, cdepths, calive):
        me = jax.lax.axis_index(AXIS)
        owned = (jnp.arange(C, dtype=jnp.int32) % S) == me
        local = ReadSet(
            bases=bases, lengths=lengths,
            mate=jnp.full(lengths.shape, -1, jnp.int32),
            insert_size=insert_size,
        )
        reps = ContigSet(bases=cbases, lengths=clens, depths=cdepths)
        ext, _walk = local_assembly.extend_contigs(
            local, reps, calive & owned, eff_c,
            mer_sizes=mer_sizes, capacity=capacity, max_ext=max_ext,
            backend=backend,
        )
        return ext.bases, ext.lengths, ext.depths

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS)) + (P(),) * 4,
        out_specs=(P(AXIS), P(AXIS), P(AXIS)),
        check_vma=False,
    )
    eb, el, ed = fn(
        localized.bases, localized.lengths, eff_loc,
        contigs.bases, contigs.lengths, contigs.depths, alive,
    )
    # combine: contig c's row comes from its owner shard (c mod S)
    owner = jnp.arange(C, dtype=jnp.int32) % S
    pick = lambda x: x.reshape((S, C) + x.shape[1:])[
        owner, jnp.arange(C, dtype=jnp.int32)
    ]
    combined = ContigSet(bases=pick(eb), lengths=pick(el), depths=pick(ed))
    return combined, overflow


# ---------------------------------------------------------------------------
# post-localization per-shard scaffolding witnesses (§III-B)
# ---------------------------------------------------------------------------


def sharded_link_candidates(
    sharded: ShardedReads,
    al,
    contigs: ContigSet,
    alive,
    mesh,
    *,
    out_factor: int = 2,
):
    """Per-shard splint/span witnesses over pair-atomically localized reads.

    Read PAIRS route together to the owner of their first aligned contig,
    carrying both alignment rows and their global indices; mate pointers
    are rebuilt on arrival from the carried indices (a dropped mate simply
    invalidates the pair — reported in the overflow count).  Each shard
    then runs the stock `candidate_links` on its local block; the
    returned flat witness arrays are already in global layout for
    `links_from_candidates`.
    """
    return _sharded_link_candidates(
        sharded, al, contigs, alive, mesh=mesh,
        insert_size=int(sharded.insert_size), out_factor=out_factor,
    )


@functools.partial(jax.jit,
                   static_argnames=("mesh", "insert_size", "out_factor"))
def _sharded_link_candidates(sharded, al, contigs, alive, *, mesh,
                             insert_size: int, out_factor: int):
    S = mesh_shards(mesh)
    R = sharded.num_reads
    assert R % S == 0

    aln = jnp.asarray(al.contig[:, :2], jnp.int32)[:R]
    mate = jnp.asarray(sharded.mate, jnp.int32)[:R]
    r = jnp.arange(R, dtype=jnp.int32)
    # pair representative = lower index of the pair (self if unpaired)
    rep = jnp.where((mate >= 0), jnp.minimum(r, mate), r)
    other = jnp.where((mate >= 0), jnp.maximum(r, mate), r)
    a_rep = aln[:, 0][rep]
    a_other = aln[:, 0][other]
    # destination contig: first aligned member of the pair; unaligned pairs
    # stay on the representative's home shard (kept together, harmless)
    dest_c = jnp.where(a_rep >= 0, a_rep, a_other)
    gidx = r
    localized, routed, overflow = localize_with(
        sharded, dest_c,
        (gidx, mate, aln, jnp.asarray(al.cstart[:, :2], jnp.int32)[:R],
         jnp.asarray(al.orient[:, :2], jnp.uint8)[:R]),
        mesh, out_factor=out_factor,
    )
    g_loc, mate_loc, c_loc, s_loc, o_loc = routed
    clens = jnp.where(alive, contigs.lengths, 0)

    def body(bases, lengths, rv, g, mg, c2, s2, o2, clens_rep):
        # rebuild mate pointers: local position of the carried global index
        n = g.shape[0]
        inv = jnp.full((R,), -1, jnp.int32).at[
            jnp.where(rv, g, R)
        ].set(jnp.arange(n, dtype=jnp.int32), mode="drop")
        new_mate = jnp.where(rv & (mg >= 0), inv[jnp.clip(mg, 0)], -1)
        local = ReadSet(
            bases=bases,
            lengths=jnp.where(rv, lengths, 0),
            mate=new_mate,
            insert_size=insert_size,
        )
        al_loc = alignment.Alignments(
            contig=jnp.where(rv[:, None], c2, -1),
            cstart=s2,
            orient=o2,
            matches=jnp.zeros_like(c2),
            overlap=jnp.zeros_like(c2),
        )
        return candidate_links(al_loc, local, clens_rep)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(AXIS),) * 8 + (P(),),
        out_specs=(P(AXIS),) * 5,
        check_vma=False,
    )
    cands = fn(
        localized.bases, localized.lengths, localized.valid,
        g_loc, mate_loc, c_loc, s_loc, o_loc, clens,
    )
    return cands, overflow
