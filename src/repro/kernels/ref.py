"""Pure-jnp oracles for every Pallas kernel in this package.

Each `*_ref` mirrors its kernel's exact interface (including output padding
conventions) using only jax.numpy and the already-tested core codecs, so
kernel tests can assert_allclose against an independent implementation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import dht, kmer
from repro.core.types import INVALID_BASE

from .kmer_extract import KmerLanes
from .mer_walk import ACTIVE, BUF_K, DEADEND, DONE, FORK, HIT, MerWalkOut


@functools.partial(jax.jit, static_argnames=("k",))
def kmer_extract_ref(bases, lengths, *, k: int) -> KmerLanes:
    """Oracle for kernels.kmer_extract (padded to [R, L]).

    Built from the independently-tested `core.kmer` codec, and kept
    BIT-identical to the Pallas kernel on every valid window — the `ref`
    backend of `kernels.ops` serves this directly, so backend parity is a
    pipeline-level guarantee, not just a kernel test
    (tests/test_kernel_parity.py).
    """
    hi, lo, valid, left, right = kmer.extract_kmers(bases, lengths, k=k)
    chi, clo, cleft, cright, flip = kmer.canonicalize_occurrences(
        hi, lo, left, right, k=k
    )
    h = kmer.kmer_hash(chi, clo)
    pad = ((0, 0), (0, k - 1))
    return KmerLanes(
        hi=jnp.pad(chi, pad),
        lo=jnp.pad(clo, pad),
        hash=jnp.pad(h, pad),
        left=jnp.pad(cleft, pad, constant_values=INVALID_BASE),
        right=jnp.pad(cright, pad, constant_values=INVALID_BASE),
        flip=jnp.pad(flip, pad),
        valid=jnp.pad(valid, pad),
    )


@functools.partial(
    jax.jit,
    static_argnames=("mer_sizes", "tag_bits", "max_ext", "min_votes",
                     "dominance", "seed_len"),
)
def mer_walk_ref(
    start_hi,
    start_lo,
    contig,
    active,
    target_hi,
    target_lo,
    keys_hi,
    keys_lo,
    used,
    max_probe,
    right_hist,
    left_hist,
    *,
    mer_sizes: tuple,
    tag_bits: int,
    max_ext: int,
    min_votes: int = 1,
    dominance: int = 4,
    seed_len: int = 0,
) -> MerWalkOut:
    """Oracle for kernels.mer_walk: the pre-fusion lax.while_loop walk.

    This IS the historical `core.local_assembly.mer_walk` body (per-step
    full-set jnp gathers through `core.dht.lookup` and the `core.kmer`
    codec), extended with the inline target-seed check the fused kernel
    performs for gap closing, and kept BIT-identical to the Pallas kernel
    (tests/test_walk_parity.py).  The per-rung table arguments are either
    the kernel's stacked [n_rungs, ...] arrays or tuples of per-rung arrays
    (what `ops.mer_walk` passes, so the tables are never copied): only
    `x[r]` is taken of them.
    """
    E = start_hi.shape[0]
    n_rungs = len(mer_sizes)
    mid_rung = n_rungs // 2
    tables = [
        dht.HashTable(slot_hi=keys_hi[r], slot_lo=keys_lo[r], used=used[r],
                      max_probe=max_probe[r])
        for r in range(n_rungs)
    ]

    def suffix(buf_hi, buf_lo, m: int):
        mask_lo, mask_hi = kmer._masks(m)
        return buf_hi & mask_hi, buf_lo & mask_lo

    def query_rung(r: int, m: int, buf_hi, buf_lo, act):
        mhi, mlo = suffix(buf_hi, buf_lo, m)
        chi, clo, flip = kmer.canonical(mhi, mlo, k=m)
        thi, tlo = kmer.embed_tag(chi, clo, contig, k=m, tag_bits=tag_bits)
        slots = dht.lookup_jnp(tables[r], thi, tlo, act)
        ok = slots >= 0
        s = jnp.clip(slots, 0)
        rsel = right_hist[r][s]
        lsel = left_hist[r][s]
        hist = jnp.where(flip[:, None], lsel[:, ::-1], rsel)
        return jnp.where(ok[:, None] & act[:, None], hist, 0)

    def choose(hist):
        c1 = hist.max(axis=-1)
        b1 = hist.argmax(axis=-1).astype(jnp.uint8)
        viable = (hist >= min_votes).sum(axis=-1)
        total = hist.sum(axis=-1)
        second = total - c1
        uncontested = (viable == 1) & (c1 >= min_votes)
        dominated = (viable > 1) & (c1 >= dominance * jnp.maximum(second, 1)) & (
            c1 >= min_votes + 1
        )
        accept = uncontested | dominated
        deadend = viable == 0
        kind = jnp.where(accept, 0, jnp.where(deadend, 1, 2))
        return b1, kind

    def cond(state):
        _, _, _, _, status, steps, _, _, _, _ = state
        return jnp.any(status == ACTIVE) & (steps < max_ext)

    def body(state):
        (buf_hi, buf_lo, rung, last_shift, status, steps, out, out_len,
         hit, hit_pos) = state
        act = status == ACTIVE
        hists = jnp.stack(
            [query_rung(r, mer_sizes[r], buf_hi, buf_lo, act)
             for r in range(n_rungs)],
            axis=1,
        )
        hist = jnp.take_along_axis(
            hists, rung[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]
        base, kind = choose(hist)
        at_top = rung == n_rungs - 1
        at_bottom = rung == 0
        stop_fork = act & (kind == 2) & (at_top | (last_shift == -1))
        stop_dead = act & (kind == 1) & (at_bottom | (last_shift == +1))
        upshift = act & (kind == 2) & ~stop_fork
        downshift = act & (kind == 1) & ~stop_dead
        accept = act & (kind == 0)
        rung = jnp.clip(rung + upshift.astype(jnp.int32)
                        - downshift.astype(jnp.int32), 0, n_rungs - 1)
        last_shift = jnp.where(
            upshift, 1, jnp.where(downshift, -1,
                                  jnp.where(accept, 0, last_shift))
        )
        nhi, nlo = kmer.append_base(buf_hi, buf_lo, base, k=BUF_K)
        buf_hi = jnp.where(accept, nhi, buf_hi)
        buf_lo = jnp.where(accept, nlo, buf_lo)
        sel = jnp.clip(out_len, 0, max_ext - 1)
        out = out.at[jnp.arange(E), sel].set(
            jnp.where(accept, base, out[jnp.arange(E), sel])
        )
        out_len = out_len + accept.astype(jnp.int32)
        status = jnp.where(stop_fork, FORK,
                           jnp.where(stop_dead, DEADEND, status))
        if seed_len > 0:
            shi, slo = suffix(buf_hi, buf_lo, seed_len)
            match = accept & (shi == target_hi) & (slo == target_lo) & ~hit
            hit_pos = jnp.where(match, out_len, hit_pos)
            hit = hit | match
            status = jnp.where(match, HIT, status)
        return (buf_hi, buf_lo, rung, last_shift, status, steps + 1, out,
                out_len, hit, hit_pos)

    init = (
        start_hi,
        start_lo,
        jnp.full((E,), mid_rung, jnp.int32),
        jnp.zeros((E,), jnp.int32),
        jnp.where(active, ACTIVE, DONE),
        jnp.int32(0),
        jnp.full((E, max_ext), 4, jnp.uint8),
        jnp.zeros((E,), jnp.int32),
        jnp.zeros((E,), bool),
        jnp.full((E,), -1, jnp.int32),
    )
    (_, _, _, _, status, _, out, out_len, hit, hit_pos) = jax.lax.while_loop(
        cond, body, init
    )
    return MerWalkOut(ext_bases=out, ext_len=out_len, status=status, hit=hit,
                      hit_pos=hit_pos)


@jax.jit
def dht_lookup_ref(slot_hi, slot_lo, used, max_probe, hi, lo, valid):
    """Oracle for kernels.dht_probe.dht_lookup: `core.dht.lookup_jnp`.

    The jnp probe chain IS the ref backend — this wrapper only adapts the
    kernel's array-level interface onto the HashTable record.
    """
    table = dht.HashTable(slot_hi=slot_hi, slot_lo=slot_lo, used=used,
                          max_probe=max_probe)
    return dht.lookup_jnp(table, hi, lo, valid)


@jax.jit
def dht_insert_ref(slot_hi, slot_lo, used, max_probe, hi, lo, valid):
    """Oracle for kernels.dht_probe.dht_insert: `core.dht.insert_jnp`.

    Both DHT refs are jitted: run eagerly, their probe `while_loop` would
    be traced and compiled anew on every call (TPU compiles of it take
    seconds), where a jitted call reuses one program per shape."""
    table = dht.HashTable(slot_hi=slot_hi, slot_lo=slot_lo, used=used,
                          max_probe=max_probe)
    out, slots = dht.insert_jnp(table, hi, lo, valid)
    return out.slot_hi, out.slot_lo, out.used, out.max_probe, slots


@functools.partial(jax.jit, static_argnames=("seed_len", "positions"))
def seed_probe_ref(bases, lengths, slot_hi, slot_lo, used, max_probe,
                   contig, pos, flip, multi, *, seed_len: int,
                   positions: tuple):
    """Oracle for kernels.seed_probe: the historical alignment front half.

    This IS the pre-fusion `alignment._candidates` gather loop plus the
    `align_reads` vote, op for op: full-width `kmer_extract_ref` lanes
    selected at the static stride columns (canonicalization commutes with
    column selection), `dht.lookup_jnp` against the seed index, candidate
    placement from the flip parity, then the O(S^2) agreement vote and the
    top-2 distinct-contig selection.  Kept BIT-identical to the Pallas
    kernel (tests/test_seed_probe_parity.py) — including the unmasked
    orient lanes of unplaced reads, which is why the kernel reproduces
    `core.kmer.append_base`'s unmasked packing of N bases.
    """
    NONE = jnp.int32(-1)
    table = dht.HashTable(slot_hi=slot_hi, slot_lo=slot_lo, used=used,
                          max_probe=max_probe)
    lanes = kmer_extract_ref(bases, lengths, k=seed_len)
    pcols = jnp.array(positions, dtype=jnp.int32)
    chi = lanes.hi[:, pcols]
    clo = lanes.lo[:, pcols]
    sval = lanes.valid[:, pcols]
    rflip = lanes.flip[:, pcols]
    slots = dht.lookup_jnp(table, chi, clo, sval)
    ok = (slots >= 0) & ~multi[jnp.clip(slots, 0)]
    cc = jnp.where(ok, contig[jnp.clip(slots, 0)], NONE)
    cpos = pos[jnp.clip(slots, 0)]
    cflip = flip[jnp.clip(slots, 0)]
    # same-strand iff the read seed and contig seed canonicalized with the
    # same flip
    same = rflip == cflip
    j = jnp.broadcast_to(pcols[None, :], cc.shape)
    L = lengths[:, None]
    cstart_fwd = cpos - j
    cstart_rc = cpos - (L - j - seed_len)
    cstart = jnp.where(same, cstart_fwd, cstart_rc)
    orient = jnp.where(same, 0, 1).astype(jnp.uint8)
    cc = jnp.where(ok, cc, NONE)
    cstart = jnp.where(ok, cstart, 0)
    # vote: support of candidate s = #seeds proposing the same placement
    agree = (
        (cc[:, :, None] == cc[:, None, :])
        & (cstart[:, :, None] == cstart[:, None, :])
        & (orient[:, :, None] == orient[:, None, :])
        & (cc[:, :, None] >= 0)
    )
    support = agree.sum(axis=-1)
    support = jnp.where(cc >= 0, support, 0)
    best = jnp.argmax(support, axis=-1)
    take = lambda a, idx: jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]
    c1, s1, o1 = take(cc, best), take(cstart, best), take(orient, best)
    # best distinct-contig second candidate
    support2 = jnp.where((cc != c1[:, None]) & (cc >= 0), support, 0)
    best2 = jnp.argmax(support2, axis=-1)
    has2 = jnp.max(support2, axis=-1) > 0
    c2 = jnp.where(has2, take(cc, best2), NONE)
    s2, o2 = take(cstart, best2), take(orient, best2)
    return (
        jnp.stack([c1, c2], axis=1),
        jnp.stack([s1, s2], axis=1),
        jnp.stack([o1, o2], axis=1),
    )


@functools.partial(jax.jit, static_argnames=("band", "match", "mismatch", "gap"))
def sw_extend_ref(query, target, qlen, tlen, *, band: int = 15,
                  match: int = 1, mismatch: int = -1, gap: int = -1):
    """Oracle for kernels.sw_extend: banded semi-global extension DP.

    Dense [QL+1, TL+1] DP (no banding shortcuts beyond masking), so the
    banded kernel must match it wherever the optimum stays inside the band.
    Returns (best_score, best_qpos, best_tpos) per batch row; positions are
    1-based DP indices (0 = empty prefix).
    """
    B, QL = query.shape
    TL = target.shape[1]
    NEGINF = jnp.int32(-(1 << 20))

    def per_row(q, t, ql, tl):
        row0 = jnp.where(
            jnp.arange(TL + 1) <= tl, jnp.arange(TL + 1, dtype=jnp.int32) * gap, NEGINF
        )
        # force band on row 0 as well: |0 - j| <= band
        row0 = jnp.where(jnp.arange(TL + 1) <= band, row0, NEGINF)

        def body(carry, i):
            prev, best, bq, bt = carry
            ii = i + 1
            sub = jnp.where(
                (q[i] == t) & (q[i] < 4), match, mismatch
            )  # [TL] score vs each target pos
            diag = prev[:-1] + sub
            up = prev[1:] + gap
            cand = jnp.maximum(diag, up)
            first = jnp.where(ii <= band, ii * gap, NEGINF)
            # left dependency: max-plus prefix scan
            def scan_fn(c, x):
                v = jnp.maximum(x, c + gap)
                return v, v

            _, row_rest = jax.lax.scan(scan_fn, first, cand)
            row = jnp.concatenate([first[None], row_rest])
            j = jnp.arange(TL + 1)
            in_band = jnp.abs(ii - j) <= band
            valid = (ii <= ql) & (j <= tl) & in_band
            row = jnp.where(valid, row, NEGINF)
            better = (row > best) & valid
            best2 = jnp.max(jnp.where(valid, row, NEGINF))
            argj = jnp.argmax(jnp.where(valid, row, NEGINF))
            upd = best2 > best
            return (
                row,
                jnp.where(upd, best2, best),
                jnp.where(upd, ii, bq),
                jnp.where(upd, argj.astype(jnp.int32), bt),
            ), None

        init = (row0, jnp.int32(0), jnp.int32(0), jnp.int32(0))
        (row, best, bq, bt), _ = jax.lax.scan(body, init, jnp.arange(QL))
        return best, bq, bt

    return jax.vmap(per_row)(query, target, qlen, tlen)


def flash_attention_ref(q, k, v, *, causal: bool = True, scale=None):
    """Oracle for kernels.flash_attention: plain softmax attention.

    q,k,v: [B, H, S, D] (k/v may have fewer heads: GQA broadcast).
    """
    B, H, S, D = q.shape
    KH = k.shape[1]
    if KH != H:
        rep = H // KH
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p, v.astype(jnp.float32)).astype(q.dtype)


def ssd_scan_ref(x, a, b, c):
    """Oracle for kernels.ssd_scan (Mamba-2 SSD, scalar-identity A).

    x: [B, S, H, P] inputs; a: [B, S, H] decay logits (A = exp(a) in (0,1));
    b, c: [B, S, H, N] input/output projections.  State: [H, P, N].
    y[t] = c[t] . state[t], state[t] = A[t] * state[t-1] + x[t] b[t]^T.
    """
    B, S, H, P = x.shape
    N = b.shape[-1]

    def step(state, inp):
        xt, at, bt, ct = inp
        state = state * at[:, :, None, None] + xt[:, :, :, None] * bt[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", state, ct)
        return state, y

    state0 = jnp.zeros((B, H, P, N), jnp.float32)
    xs = (
        jnp.moveaxis(x, 1, 0).astype(jnp.float32),
        jnp.moveaxis(jnp.exp(a), 1, 0).astype(jnp.float32),
        jnp.moveaxis(b, 1, 0).astype(jnp.float32),
        jnp.moveaxis(c, 1, 0).astype(jnp.float32),
    )
    _, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)  # [B, S, H, P]
