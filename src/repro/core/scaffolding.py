"""Scaffolding (paper §III, Algorithm 3).

Stages, each mapped to its TPU-idiomatic form:
  1. splint detection  — reads whose two verified alignment hits land on
     different contigs (§III-B); pure per-read arithmetic on the aligner's
     top-2 hits.
  2. span detection    — mate pairs on different contigs; gap estimated
     from the library insert size (§III-B).
  3. link aggregation  — the paper's distributed hash table keyed by contig
     pairs becomes a sort + segment-reduce over packed (endA, endB) keys
     (UC1 + UC4, same argument as k-mer counting).
  4. repeat suspension — span links that "jump over" a short repeat contig
     suspend it (§III-C), re-exposing extendable ends.
  5. traversal         — the sequential longest-seed-first walk becomes
     deterministic parallel greedy matching: every end proposes its best
     incident link, a link locks iff both ends chose it, repeat.  Priority
     order (longer contig first, then closer gap, then support) reproduces
     the sequential heuristic's choices on conflict-free neighborhoods.
  6. connected components (cc.py) partition the contig graph exactly as in
     the paper — used here to bound matching rounds and by the distributed
     runtime to place components.
  7. chain formation   — matched ends form an oriented functional graph;
     chain.py contracts it (same machinery as the DBG traversal).

HMM-hit contigs (conserved rRNA regions, §III-C): ends of contigs flagged
by the profile-HMM scorer (core/hmm.py) stay extendable under competing
links, preferring similar-depth HMM-hit partners.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import chain, cc
from .types import ContigSet, ReadSet

NONE = jnp.int32(-1)
BIG = jnp.int32(0x7FFFFFFF)
MAX_LINKS_PER_END = 4


class Links(NamedTuple):
    """Aggregated contig-end links, dense [Emax]."""

    end_a: jnp.ndarray    # [E] int32 packed end id (contig*2 + end), a < b
    end_b: jnp.ndarray    # [E] int32
    gap: jnp.ndarray      # [E] float32 estimated gap (can be negative)
    support: jnp.ndarray  # [E] int32 #splints + #spans
    splints: jnp.ndarray  # [E] int32 #splint witnesses
    valid: jnp.ndarray    # [E] bool


class Scaffolds(NamedTuple):
    """Chains of oriented contigs."""

    contig: jnp.ndarray   # [S, M] int32 member contig ids (-1 pad)
    orient: jnp.ndarray   # [S, M] uint8 0=fwd, 1=rc within the scaffold
    gap: jnp.ndarray      # [S, M] float32 gap AFTER member j (last = 0)
    n_members: jnp.ndarray  # [S] int32
    n_scaffolds: jnp.ndarray  # scalar int32


def _hit_read_interval(cstart, orient, clen, read_len):
    """Read-frame interval [a, b) covered by the contig in this hit."""
    a_fwd = -cstart
    b_fwd = clen - cstart
    a_rc = read_len - cstart - clen
    b_rc = read_len - cstart
    a = jnp.where(orient == 0, a_fwd, a_rc)
    b = jnp.where(orient == 0, b_fwd, b_rc)
    return a, b


def _outward_end(orient, read_right: bool):
    """Which contig end faces read-right (True) / read-left."""
    # orient==0: read-right == contig-right (end 1)
    e_right = jnp.where(orient == 0, 1, 0)
    return e_right if read_right else 1 - e_right


def find_splints(al, reads: ReadSet, contig_lengths):
    """Per-read splint candidate: (endA, endB, gap, valid)."""
    c0, c1 = al.contig[:, 0], al.contig[:, 1]
    both = (c0 >= 0) & (c1 >= 0) & (c0 != c1)
    L = reads.lengths
    cl0 = contig_lengths[jnp.clip(c0, 0)]
    cl1 = contig_lengths[jnp.clip(c1, 0)]
    a0, b0 = _hit_read_interval(al.cstart[:, 0], al.orient[:, 0], cl0, L)
    a1, b1 = _hit_read_interval(al.cstart[:, 1], al.orient[:, 1], cl1, L)
    # order along the read; require a real bridge (no containment)
    first_is_0 = a0 <= a1
    gap = jnp.where(first_is_0, a1 - b0, a0 - b1)
    cf = jnp.where(first_is_0, c0, c1)
    cs = jnp.where(first_is_0, c1, c0)
    of = jnp.where(first_is_0, al.orient[:, 0], al.orient[:, 1])
    os_ = jnp.where(first_is_0, al.orient[:, 1], al.orient[:, 0])
    # end of the first contig facing read-right; end of second facing left
    ef = jnp.where(of == 0, 1, 0)
    es = jnp.where(os_ == 0, 0, 1)
    end_f = cf * 2 + ef
    end_s = cs * 2 + es
    valid = both & (gap > -int(reads.max_len)) & (gap < int(reads.max_len))
    # normalize unordered pair
    ea = jnp.minimum(end_f, end_s)
    eb = jnp.maximum(end_f, end_s)
    return ea, eb, gap.astype(jnp.float32), valid


def find_spans(al, reads: ReadSet, contig_lengths):
    """Per-pair span candidate from mate alignments (counted once)."""
    r = jnp.arange(reads.num_reads, dtype=jnp.int32)
    m = reads.mate
    has_mate = (m >= 0) & (r < m)  # count each pair once
    c_r = al.contig[:, 0]
    c_m = jnp.where(m >= 0, al.contig[jnp.clip(m, 0), 0], NONE)
    both = has_mate & (c_r >= 0) & (c_m >= 0) & (c_r != c_m)
    L = reads.lengths
    cl_r = contig_lengths[jnp.clip(c_r, 0)]
    cl_m = contig_lengths[jnp.clip(c_m, 0)]
    o_r = al.orient[:, 0]
    o_m = al.orient[jnp.clip(m, 0), 0]
    s_r = al.cstart[:, 0]
    s_m = al.cstart[jnp.clip(m, 0), 0]
    # distance from fragment start to the contig end in fragment direction
    d_r = jnp.where(o_r == 0, cl_r - s_r, s_r + L)
    d_m = jnp.where(o_m == 0, cl_m - s_m, s_m + jnp.where(m >= 0, L[jnp.clip(m, 0)], 0))
    gap = reads.insert_size - d_r - d_m
    e_r = c_r * 2 + jnp.where(o_r == 0, 1, 0)
    e_m = c_m * 2 + jnp.where(o_m == 0, 1, 0)
    ea = jnp.minimum(e_r, e_m)
    eb = jnp.maximum(e_r, e_m)
    valid = both & (gap > -2.0 * reads.insert_size) & (gap < 2.0 * reads.insert_size)
    return ea, eb, gap.astype(jnp.float32), valid


@functools.partial(jax.jit, static_argnames=("capacity",))
def aggregate_links(ea, eb, gap, valid, is_splint, *, capacity: int) -> Links:
    """Sort + segment-reduce witnesses into per-pair links (§III-B)."""
    key_a = jnp.where(valid, ea, BIG)
    key_b = jnp.where(valid, eb, BIG)
    idx = jnp.arange(ea.shape[0], dtype=jnp.int32)
    # idx as the last key gives a stable sort's order from an unstable
    # sort, which XLA:TPU compiles faster
    ka, kb, perm = jax.lax.sort((key_a, key_b, idx), num_keys=3,
                                is_stable=False)
    g = gap[perm]
    sp = is_splint[perm]
    v = valid[perm]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (ka[1:] != ka[:-1]) | (kb[1:] != kb[:-1])]
    )
    new_grp = v & first
    seg = jnp.cumsum(new_grp.astype(jnp.int32)) - 1
    seg_d = jnp.where(v, seg, capacity)
    support = jnp.zeros((capacity,), jnp.int32).at[seg_d].add(1, mode="drop")
    splints = jnp.zeros((capacity,), jnp.int32).at[seg_d].add(
        sp.astype(jnp.int32), mode="drop"
    )
    gap_sum = jnp.zeros((capacity,), jnp.float32).at[seg_d].add(g, mode="drop")
    out_a = jnp.full((capacity,), NONE).at[jnp.where(new_grp, seg, capacity)].set(
        ka, mode="drop"
    )
    out_b = jnp.full((capacity,), NONE).at[jnp.where(new_grp, seg, capacity)].set(
        kb, mode="drop"
    )
    return Links(
        end_a=out_a,
        end_b=out_b,
        gap=gap_sum / jnp.maximum(support.astype(jnp.float32), 1.0),
        support=support,
        splints=splints,
        valid=support > 0,
    )


@jax.jit
def candidate_links(al, reads: ReadSet, contig_lengths):
    """Per-read link witnesses: splints + spans as flat candidate arrays.

    This is the read-proportional half of link building — pure per-read
    arithmetic over the aligner's hits, no contig-graph state.  On a mesh it
    runs per shard over that shard's (localized) read block (DESIGN.md §6);
    the returned (end_a, end_b, gap, valid, is_splint) arrays concatenate
    across shards before `links_from_candidates`.
    """
    sa, sb, sg, sv = find_splints(al, reads, contig_lengths)
    pa, pb, pg, pv = find_spans(al, reads, contig_lengths)
    ea = jnp.concatenate([sa, pa])
    eb = jnp.concatenate([sb, pb])
    gap = jnp.concatenate([sg, pg])
    valid = jnp.concatenate([sv, pv])
    is_splint = jnp.concatenate([jnp.ones_like(sv), jnp.zeros_like(pv)])
    return ea, eb, gap, valid, is_splint


@functools.partial(jax.jit, static_argnames=("capacity", "min_support"))
def links_from_candidates(ea, eb, gap, valid, is_splint, alive, *,
                          capacity: int, min_support: int = 2) -> Links:
    """Aggregate candidate witnesses into the link store (contig scale)."""
    # drop links touching dead contigs
    ca = jnp.clip(ea // 2, 0)
    cb2 = jnp.clip(eb // 2, 0)
    valid = valid & alive[ca] & alive[cb2]
    links = aggregate_links(ea, eb, gap, valid, is_splint, capacity=capacity)
    # the paper prunes low-multiplicity links BEFORE CC to expose parallelism
    return links._replace(valid=links.valid & (links.support >= min_support))


def build_links(al, reads: ReadSet, contigs: ContigSet, alive, *,
                capacity: int, min_support: int = 2) -> Links:
    clens = jnp.where(alive, contigs.lengths, 0)
    cands = candidate_links(al, reads, clens)
    return links_from_candidates(
        *cands, alive, capacity=capacity, min_support=min_support
    )


def _per_end_links(links: Links, n_ends: int):
    """Top-MAX_LINKS_PER_END incident links per end, by gap ascending.

    Returns (link_idx [n_ends, K], count [n_ends]).
    """
    E = links.end_a.shape[0]
    # each link appears at both ends
    ends = jnp.concatenate([links.end_a, links.end_b])
    lidx = jnp.tile(jnp.arange(E, dtype=jnp.int32), 2)
    gaps = jnp.tile(links.gap, 2)
    v = jnp.tile(links.valid, 2)
    key_end = jnp.where(v, ends, BIG)
    # sort by (end, gap): quantize gap into the sort key
    gap_q = jnp.clip(gaps, -1e6, 1e6).astype(jnp.float32)
    # lidx is unique per end, so as the last key it keeps the stable order
    sk_end, sk_gap, s_lidx = jax.lax.sort((key_end, gap_q, lidx), num_keys=3,
                                          is_stable=False)
    first = jnp.concatenate([jnp.ones((1,), bool), sk_end[1:] != sk_end[:-1]])
    # rank within the end group
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1
    pos_in_seg = jnp.arange(2 * E, dtype=jnp.int32) - jnp.zeros(
        (2 * E,), jnp.int32
    ).at[jnp.where(first, seg, 2 * E)].set(
        jnp.arange(2 * E, dtype=jnp.int32), mode="drop"
    )[seg]
    out = jnp.full((n_ends, MAX_LINKS_PER_END), NONE)
    valid_row = sk_end < BIG
    sel_e = jnp.where(valid_row & (pos_in_seg < MAX_LINKS_PER_END), sk_end, n_ends)
    sel_k = jnp.clip(pos_in_seg, 0, MAX_LINKS_PER_END - 1)
    out = out.at[sel_e, sel_k].set(s_lidx, mode="drop")
    count = jnp.zeros((n_ends,), jnp.int32).at[
        jnp.where(valid_row, sk_end, n_ends)
    ].add(1, mode="drop")
    return out, count


def suspend_repeats(links: Links, contig_lengths, insert_size, n_ends: int):
    """§III-C repeat suspension: a span jumping x—z over a short contig y
    (linked x—y and y—z) suspends y, removing its competing links."""
    end_links, end_cnt = _per_end_links(links, n_ends)
    E = links.end_a.shape[0]

    def other_end(lidx, my_end):
        a = links.end_a[lidx]
        b = links.end_b[lidx]
        return jnp.where(a == my_end, b, a)

    ends = jnp.arange(n_ends, dtype=jnp.int32)
    # consider the two closest links per end: y = closest, z = next
    l0 = end_links[:, 0]
    l1 = end_links[:, 1]
    have2 = (l0 >= 0) & (l1 >= 0)
    y_end = other_end(jnp.clip(l0, 0), ends)      # near partner end
    z_end = other_end(jnp.clip(l1, 0), ends)
    y_c = y_end // 2
    y_far_end = y_c * 2 + (1 - (y_end & 1))
    y_len = contig_lengths[jnp.clip(y_c, 0)].astype(jnp.float32)
    g_y = links.gap[jnp.clip(l0, 0)]
    g_z = links.gap[jnp.clip(l1, 0)]
    # geometric consistency: z sits roughly one y further out
    consistent = jnp.abs(g_z - (g_y + y_len)) <= 0.75 * insert_size
    short_enough = y_len <= insert_size
    # require an existing link between y's far end and z's end
    has_yz = jnp.zeros((n_ends,), bool)
    for k in range(MAX_LINKS_PER_END):
        lk = end_links[jnp.clip(y_far_end, 0), k]
        partner = other_end(jnp.clip(lk, 0), y_far_end)
        has_yz = has_yz | ((lk >= 0) & (partner == z_end))
    suspend_y = have2 & consistent & short_enough & has_yz
    suspended = jnp.zeros((n_ends // 2,), bool).at[
        jnp.where(suspend_y, y_c, n_ends // 2)
    ].set(True, mode="drop")
    # drop all links touching suspended contigs
    la_c = jnp.clip(links.end_a // 2, 0)
    lb_c = jnp.clip(links.end_b // 2, 0)
    new_valid = links.valid & ~suspended[la_c] & ~suspended[lb_c]
    return links._replace(valid=new_valid), suspended


@functools.partial(jax.jit, static_argnames=("n_ends", "rounds"))
def greedy_matching(links: Links, contig_lengths, hmm_hit, *, n_ends: int,
                    rounds: int = 16):
    """Parallel greedy matching = the paper's longest-seed-first traversal.

    Link priority: (longer min-member first, then closer gap, then higher
    support).  Ends with competing links are not extendable (conservative
    metagenome rule) unless their contig is an HMM hit (§III-C rRNA rule).
    """
    E = links.end_a.shape[0]
    end_links, end_cnt = _per_end_links(links, n_ends)
    ca = jnp.clip(links.end_a // 2, 0)
    cb2 = jnp.clip(links.end_b // 2, 0)
    minlen = jnp.minimum(contig_lengths[ca], contig_lengths[cb2])
    # rank: smaller is better
    order = jnp.argsort(
        -(minlen.astype(jnp.float32) * 1e6)
        + jnp.clip(links.gap, 0, 1e5)
        - links.support.astype(jnp.float32) * 10.0
    )
    rank = jnp.zeros((E,), jnp.int32).at[order].set(jnp.arange(E, dtype=jnp.int32))
    rank = jnp.where(links.valid, rank, BIG)
    # extendability: <=1 live link, or HMM-hit contig
    live_cnt = end_cnt
    contig_of_end = jnp.arange(n_ends, dtype=jnp.int32) // 2
    extendable = (live_cnt <= 1) | hmm_hit[contig_of_end]
    ok_a = extendable[jnp.clip(links.end_a, 0)]
    ok_b = extendable[jnp.clip(links.end_b, 0)]
    eligible = links.valid & ok_a & ok_b
    rank = jnp.where(eligible, rank, BIG)

    def body(_, state):
        matched_end, link_used = state
        free_a = matched_end[jnp.clip(links.end_a, 0)] == NONE
        free_b = matched_end[jnp.clip(links.end_b, 0)] == NONE
        live = eligible & ~link_used & free_a & free_b
        r = jnp.where(live, rank, BIG)
        # each end's best live incident link
        best = jnp.full((n_ends,), BIG)
        best = best.at[jnp.where(live, links.end_a, n_ends)].min(r, mode="drop")
        best = best.at[jnp.where(live, links.end_b, n_ends)].min(r, mode="drop")
        win = live & (best[jnp.clip(links.end_a, 0)] == r) & (
            best[jnp.clip(links.end_b, 0)] == r
        )
        matched_end = matched_end.at[jnp.where(win, links.end_a, n_ends)].set(
            links.end_b, mode="drop"
        )
        matched_end = matched_end.at[jnp.where(win, links.end_b, n_ends)].set(
            links.end_a, mode="drop"
        )
        return matched_end, link_used | win

    matched_end, link_used = jax.lax.fori_loop(
        0, rounds, body, (jnp.full((n_ends,), NONE), jnp.zeros((E,), bool))
    )
    # gap per matched end
    end_gap = jnp.zeros((n_ends,), jnp.float32)
    end_gap = end_gap.at[jnp.where(link_used, links.end_a, n_ends)].set(
        links.gap, mode="drop"
    )
    end_gap = end_gap.at[jnp.where(link_used, links.end_b, n_ends)].set(
        links.gap, mode="drop"
    )
    return matched_end, end_gap


@functools.partial(jax.jit, static_argnames=("n_contigs", "max_members"))
def form_scaffolds(matched_end, end_gap, alive, *, n_contigs: int,
                   max_members: int) -> Scaffolds:
    """Contract matched contig ends into oriented scaffold chains."""
    C = n_contigs
    # oriented contig nodes: dir 0 = ->, exits end 1; dir 1 = <-, exits end 0
    # succ(c, d): partner of exit end; entry end 0 => dir 0, entry 1 => dir 1
    cidx = jnp.arange(C, dtype=jnp.int32)
    exit_end = jnp.concatenate([cidx * 2 + 1, cidx * 2])      # dir0, dir1
    partner = matched_end[exit_end]                            # [2C]
    has = (partner >= 0) & jnp.tile(alive, 2)
    p_c = jnp.clip(partner, 0) // 2
    p_entry = jnp.clip(partner, 0) & 1
    succ = jnp.where(has & alive[p_c], p_c + p_entry * C, NONE)
    u = jnp.arange(2 * C, dtype=jnp.int32)
    rc = (u + C) % (2 * C)
    succ_rc = succ[rc]
    pred = jnp.where(succ_rc >= 0, (succ_rc + C) % (2 * C), NONE)
    alive2 = jnp.tile(alive, 2)
    chains = chain.form_chains(jnp.where(alive2, pred, NONE))
    head_self = chains.head
    head_rc = chains.head[rc]
    keep = alive2 & (head_self <= head_rc)
    is_head = keep & (chains.dist == 0)
    sid_of_head = jnp.cumsum(is_head.astype(jnp.int32)) - 1
    n_scaffolds = jnp.where(jnp.any(is_head), sid_of_head[-1] + 1, 0)
    sid_all = jnp.where(is_head, sid_of_head, NONE)
    node_sid = jnp.where(keep, sid_all[chains.head], NONE)
    S = C  # scaffold capacity = contig capacity
    contig_arr = jnp.full((S, max_members), NONE)
    orient_arr = jnp.zeros((S, max_members), jnp.uint8)
    gap_arr = jnp.zeros((S, max_members), jnp.float32)
    ok = keep & (node_sid >= 0) & (chains.dist < max_members)
    row = jnp.where(ok, node_sid, S)
    col = jnp.clip(chains.dist, 0, max_members - 1)
    node_c = u % C
    node_dir = (u // C).astype(jnp.uint8)
    contig_arr = contig_arr.at[row, col].set(node_c, mode="drop")
    orient_arr = orient_arr.at[row, col].set(node_dir, mode="drop")
    # gap after member = gap recorded at its exit end
    gap_arr = gap_arr.at[row, col].set(end_gap[exit_end], mode="drop")
    n_members = jnp.zeros((S,), jnp.int32).at[row].add(1, mode="drop")
    return Scaffolds(
        contig=contig_arr,
        orient=orient_arr,
        gap=gap_arr,
        n_members=n_members,
        n_scaffolds=n_scaffolds,
    )


@functools.partial(jax.jit, static_argnames=("max_members",))
def scaffold_from_links(
    links: Links,
    contigs: ContigSet,
    alive,
    insert_size: float,
    *,
    max_members: int = 32,
    hmm_hit=None,
):
    """Contig-scale half of Algorithm 3: suspension -> CC -> matching ->
    chain formation.  Runs replicated on a mesh (contig state is small);
    the read-proportional link witnesses arrive via `candidate_links`."""
    C = contigs.capacity
    n_ends = 2 * C
    links, suspended = suspend_repeats(
        links, contigs.lengths, jnp.asarray(insert_size, jnp.float32),
        n_ends
    )
    if hmm_hit is None:
        hmm_hit = jnp.zeros((C,), bool)
    alive_eff = alive & ~suspended
    # component labels bound matching rounds & drive distributed placement
    comp = cc.connected_components(
        jnp.clip(links.end_a // 2, 0), jnp.clip(links.end_b // 2, 0),
        links.valid, C,
    )
    matched_end, end_gap = greedy_matching(
        links, jnp.where(alive_eff, contigs.lengths, 0), hmm_hit, n_ends=n_ends
    )
    scaffs = form_scaffolds(
        matched_end, end_gap, alive_eff, n_contigs=C, max_members=max_members
    )
    return scaffs, links, suspended, comp


def scaffold(
    al,
    reads: ReadSet,
    contigs: ContigSet,
    alive,
    *,
    link_capacity: int = 1 << 12,
    min_support: int = 2,
    max_members: int = 32,
    hmm_hit=None,
):
    """Algorithm 3 minus gap closing (see gap_closing.py)."""
    links = build_links(
        al, reads, contigs, alive, capacity=link_capacity, min_support=min_support
    )
    return scaffold_from_links(
        links, contigs, alive, float(reads.insert_size),
        max_members=max_members, hmm_hit=hmm_hit,
    )
