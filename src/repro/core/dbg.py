"""De Bruijn graph traversal -> contigs (paper §II-C).

A contig is a maximal path of k-mers with mutually-agreeing unique
high-quality extensions.  MetaHipMer walks these paths with a distributed
hash table + atomics; here the graph is contracted with oriented pointer
doubling (see chain.py and DESIGN.md §2).

Orientation handling uses the doubled-graph trick: each canonical k-mer i
yields two oriented nodes, u = i (as stored) and u = i + N (reverse
complement).  succ(u) follows the oriented right extension; an edge
survives only if the reverse edge agrees (succ(rc(v)) == rc(u)), which is
exactly the paper's bidirectional-agreement rule and guarantees the
resulting graph is functional in both directions.  Every chain then appears
exactly twice (once per strand); the representative with the smaller head
index is emitted.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import chain, dht, kmer
from .types import ContigSet, EXT_F, EXT_X, KmerSet

NONE = jnp.int32(-1)


class KmerIndex(NamedTuple):
    """Hash table over the live k-mers of a KmerSet, mapping key -> row."""

    table: dht.HashTable
    slot_to_row: jnp.ndarray  # [table_cap] int32


@functools.partial(jax.jit, static_argnames=("table_capacity",))
def build_index(kset: KmerSet, table_capacity: int | None = None) -> KmerIndex:
    cap = table_capacity or 2 * kset.capacity
    table, slots = dht.build(kset.hi, kset.lo, kset.used, capacity=cap)
    rows = jnp.arange(kset.capacity, dtype=jnp.int32)
    slot_to_row = jnp.full((cap,), NONE).at[
        jnp.where(slots >= 0, slots, cap)
    ].set(rows, mode="drop")
    return KmerIndex(table=table, slot_to_row=slot_to_row)


def lookup_rows(index: KmerIndex, hi, lo, valid=None):
    slots = dht.lookup(index.table, hi, lo, valid)
    return jnp.where(slots >= 0, index.slot_to_row[slots], NONE)


def _oriented_code(kset: KmerSet, *, k: int):
    """Packed code of both orientations: [2N] (hi, lo)."""
    rhi, rlo = kmer.reverse_complement(kset.hi, kset.lo, k=k)
    return (
        jnp.concatenate([kset.hi, rhi]),
        jnp.concatenate([kset.lo, rlo]),
    )


def _oriented_ext(kset: KmerSet):
    """Right extension code in each orientation's reading frame: [2N]."""
    fwd_right = kset.right_ext
    # reading the RC strand: right ext = complement of the stored LEFT ext
    rc_right = jnp.where(
        kset.left_ext < 4, (3 - kset.left_ext).astype(jnp.uint8), kset.left_ext
    )
    return jnp.concatenate([fwd_right, rc_right])


def oriented_successors(kset: KmerSet, index: KmerIndex, *, k: int):
    """succ[u] for all 2N oriented nodes, after mutual-agreement masking."""
    n = kset.capacity
    ohi, olo = _oriented_code(kset, k=k)
    rext = _oriented_ext(kset)
    alive = jnp.concatenate([kset.used, kset.used])
    has_ext = alive & (rext < 4)
    nhi, nlo = kmer.append_base(ohi, olo, rext & 3, k=k)
    chi, clo, flip = kmer.canonical(nhi, nlo, k=k)
    row = lookup_rows(index, chi, clo, has_ext)
    succ = jnp.where(
        (row >= 0) & has_ext, row + flip.astype(jnp.int32) * n, NONE
    )
    # mutual agreement: succ(rc(v)) must equal rc(u)
    u = jnp.arange(2 * n, dtype=jnp.int32)
    rc_node = lambda x: jnp.where(x >= 0, (x + n) % (2 * n), NONE)
    v = succ
    succ_rc_v = jnp.where(v >= 0, succ[rc_node(v)], NONE)
    mutual = (v >= 0) & (succ_rc_v == rc_node(u))
    return jnp.where(mutual, v, NONE)


class Traversal(NamedTuple):
    contigs: ContigSet
    # per oriented node: emitted contig id (-1 if not on an emitted strand)
    node_contig: jnp.ndarray   # [2N] int32
    node_pos: jnp.ndarray      # [2N] int32 offset within the contig
    n_contigs: jnp.ndarray     # scalar int32
    overflow: jnp.ndarray      # scalar bool (contig count or length cap hit)


@functools.partial(jax.jit, static_argnames=("k", "contig_cap", "max_len"))
def traverse(
    kset: KmerSet,
    index: KmerIndex,
    *,
    k: int,
    contig_cap: int,
    max_len: int,
) -> Traversal:
    """Contract unique-extension paths into contigs."""
    n = kset.capacity
    succ = oriented_successors(kset, index, k=k)
    # pred via strand symmetry: pred(u) = rc(succ(rc(u)))
    u = jnp.arange(2 * n, dtype=jnp.int32)
    rc = (u + n) % (2 * n)
    succ_rc = succ[rc]
    pred = jnp.where(succ_rc >= 0, (succ_rc + n) % (2 * n), NONE)
    alive = jnp.concatenate([kset.used, kset.used])
    chains = chain.form_chains(jnp.where(alive, pred, NONE))
    length_nodes = chain.chain_stats(chains, alive)
    # one strand per contig: keep the chain whose head index is the smaller
    # of (own head, RC-chain head); RC-chain head of u's chain = head[rc(u)]
    head_self = chains.head
    head_rc = chains.head[rc]
    # == case: RC-palindromic chain (contains its own RC) — kept once
    keep = alive & (head_self <= head_rc)
    # enumerate contigs by their head nodes
    is_head = keep & (chains.dist == 0)
    cid_of_head = jnp.cumsum(is_head.astype(jnp.int32)) - 1
    n_contigs = jnp.where(jnp.any(is_head), cid_of_head[-1] + 1, 0)
    cid_all = jnp.where(is_head, cid_of_head, NONE)
    node_cid = jnp.where(keep, cid_all[chains.head], NONE)
    # base emission
    ohi, olo = _oriented_code(kset, k=k)
    last = kmer.last_base(ohi, olo, k=k)  # oriented last base, [2N]
    bases = jnp.full((contig_cap, max_len), 4, jnp.uint8)
    # head writes its k bases: contig c's head is the c-th head node, so
    # decode just those [contig_cap] k-mers into the first k columns
    heads = jnp.nonzero(is_head, size=contig_cap, fill_value=0)[0]
    head_kmer = kmer.decode(ohi[heads], olo[heads], k=k)  # [contig_cap, k]
    has_head = jnp.arange(contig_cap) < n_contigs
    bases = bases.at[:, :k].set(jnp.where(has_head[:, None], head_kmer, 4))
    # non-head nodes write one base at k-1+dist
    tail_sel = keep & (chains.dist > 0) & (node_cid >= 0)
    row_idx = jnp.where(tail_sel, node_cid, contig_cap)
    col_idx = jnp.where(tail_sel, k - 1 + chains.dist, 0)
    in_range = col_idx < max_len
    row_idx = jnp.where(in_range, row_idx, contig_cap)
    bases = bases.at[row_idx, col_idx].set(last, mode="drop")
    # lengths + depths
    clen_nodes = jnp.full((contig_cap,), 0, jnp.int32).at[
        jnp.where(is_head, cid_all, contig_cap)
    ].set(length_nodes, mode="drop")
    lengths = jnp.where(clen_nodes > 0, jnp.minimum(clen_nodes + k - 1, max_len), 0)
    counts2 = jnp.concatenate([kset.count, kset.count]).astype(jnp.float32)
    seg = jnp.where(node_cid >= 0, node_cid, contig_cap)
    depth_sum = jnp.zeros((contig_cap,), jnp.float32).at[seg].add(
        jnp.where(keep, counts2, 0.0), mode="drop"
    )
    depths = depth_sum / jnp.maximum(clen_nodes.astype(jnp.float32), 1.0)
    overflow = (n_contigs > contig_cap) | jnp.any(
        keep & (k - 1 + chains.dist >= max_len)
    )
    return Traversal(
        contigs=ContigSet(bases=bases, lengths=lengths, depths=depths),
        node_contig=node_cid,
        node_pos=chains.dist,
        n_contigs=n_contigs,
        overflow=overflow,
    )


@functools.partial(jax.jit, static_argnames=("k", "contig_cap"))
def end_neighbor_forks(
    kset: KmerSet, index: KmerIndex, trav: Traversal, *, k: int, contig_cap: int
):
    """For each contig end, the k-mer rows reachable one step past the end.

    Returns [contig_cap, 2, 4] int32 rows (-1 = absent): entry [c, 0, b] is
    the row of the k-mer obtained by extending the contig's head leftward
    with base b (in the contig's reading frame); [c, 1, b] extends the tail
    rightward.  These "fork" vertices carry the contig-graph connectivity
    used by bubble merging (§II-D) and pruning (§II-E).
    """
    n = kset.capacity
    ohi, olo = _oriented_code(kset, k=k)
    alive = jnp.concatenate([kset.used, kset.used])
    is_end = (trav.node_contig >= 0) & alive
    out = jnp.full((contig_cap, 2, 4), NONE)
    chains_head_mask = is_end & (trav.node_pos == 0)
    # tail: node whose succ is NONE within its contig — recompute succ
    succ = oriented_successors(kset, index, k=k)
    tails_mask = is_end & (succ == NONE)
    for b in range(4):
        bb = jnp.full((2 * n,), b, jnp.uint8)
        # tail side: append base b
        nhi, nlo = kmer.append_base(ohi, olo, bb, k=k)
        chi2, clo2, _ = kmer.canonical(nhi, nlo, k=k)
        row_t = lookup_rows(index, chi2, clo2, tails_mask)
        sel = jnp.where(tails_mask & (row_t >= 0), trav.node_contig, contig_cap)
        out = out.at[sel, 1, b].set(row_t, mode="drop")
        # head side: prepend base b
        phi, plo = kmer.prepend_base(ohi, olo, bb, k=k)
        chi3, clo3, _ = kmer.canonical(phi, plo, k=k)
        row_h = lookup_rows(index, chi3, clo3, chains_head_mask)
        sel = jnp.where(chains_head_mask & (row_h >= 0), trav.node_contig, contig_cap)
        out = out.at[sel, 0, b].set(row_h, mode="drop")
    return out
