"""Dual-lane (hi, lo) uint32 k-mer codec, k <= 31.

TPU adaptation: the CPU/GPU assembly literature packs k-mers into uint64.
TPUs (and the XLA TPU backend) have no fast 64-bit integer path, so every
k-mer code here is a pair of uint32 lanes holding a 62-bit value
(code = hi * 2**32 + lo).  All operations — append/prepend a base, reverse
complement, canonicalization, mix-hash — are written as 32-bit lane ops with
static (Python-int) shift amounts so they vectorize on the VPU.

Bases are packed MSB-first: the FIRST base of the k-mer sits in the highest
2 bits of the 2k-bit code.  This makes lexicographic order of the packed
value equal to lexicographic order of the string, which canonicalization
relies on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .types import INVALID_BASE

U32 = jnp.uint32
MAX_K = 31


def _masks(k: int):
    """Static (lo, hi) masks for a 2k-bit code."""
    assert 1 <= k <= MAX_K, f"k={k} out of range (1..{MAX_K})"
    bits = 2 * k
    if bits >= 32:
        mask_lo = 0xFFFFFFFF
        mask_hi = (1 << (bits - 32)) - 1
    else:
        mask_lo = (1 << bits) - 1
        mask_hi = 0
    return U32(mask_lo), U32(mask_hi)


def append_base(hi, lo, base, *, k: int):
    """code' = ((code << 2) | base) masked to 2k bits (drop oldest base)."""
    mask_lo, mask_hi = _masks(k)
    new_hi = ((hi << 2) | (lo >> 30)) & mask_hi
    new_lo = ((lo << 2) | base.astype(U32)) & mask_lo
    return new_hi, new_lo


def prepend_base(hi, lo, base, *, k: int):
    """code' = (code >> 2) | (base << 2*(k-1)) (drop newest base)."""
    b = base.astype(U32)
    new_lo = (lo >> 2) | (hi << 30)
    new_hi = hi >> 2
    shift = 2 * (k - 1)
    if shift >= 32:
        new_hi = new_hi | (b << (shift - 32))
    else:
        new_lo = new_lo | (b << shift)
        mask_lo, mask_hi = _masks(k)
        new_lo = new_lo & mask_lo
        new_hi = new_hi & mask_hi
    return new_hi, new_lo


def first_base(hi, lo, *, k: int):
    shift = 2 * (k - 1)
    if shift >= 32:
        return ((hi >> (shift - 32)) & 3).astype(jnp.uint8)
    return ((lo >> shift) & 3).astype(jnp.uint8)


def last_base(hi, lo, *, k: int):
    del k
    return (lo & 3).astype(jnp.uint8)


def _rev32_2bit(x):
    """Reverse the 16 two-bit groups inside each uint32 lane."""
    x = ((x & U32(0x33333333)) << 2) | ((x >> 2) & U32(0x33333333))
    x = ((x & U32(0x0F0F0F0F)) << 4) | ((x >> 4) & U32(0x0F0F0F0F))
    x = ((x & U32(0x00FF00FF)) << 8) | ((x >> 8) & U32(0x00FF00FF))
    x = (x << 16) | (x >> 16)
    return x


def _shift_right_64(hi, lo, s: int):
    """(hi,lo) >> s with static s in [0, 63]."""
    if s == 0:
        return hi, lo
    if s >= 32:
        return jnp.zeros_like(hi), hi >> (s - 32)
    return hi >> s, (lo >> s) | (hi << (32 - s))


def reverse_complement(hi, lo, *, k: int):
    """RC of a packed k-mer: complement each base, reverse base order."""
    mask_lo, mask_hi = _masks(k)
    # complement: each valid 2-bit group XOR 0b11 == full-lane XOR then mask
    clo = (~lo) & mask_lo
    if k <= 16:
        # value lives entirely in lo; reverse within the lane, shift down
        r = _rev32_2bit(clo)
        rlo = r >> (32 - 2 * k) if k < 16 else r
        return jnp.zeros_like(hi), rlo
    chi = (~hi) & mask_hi
    # 64-bit reverse: swap lanes and reverse each
    rhi64 = _rev32_2bit(clo)
    rlo64 = _rev32_2bit(chi)
    # reversed value occupies top 2k bits of 64; shift right by 64 - 2k
    return _shift_right_64(rhi64, rlo64, 64 - 2 * k)


def less(hi_a, lo_a, hi_b, lo_b):
    return (hi_a < hi_b) | ((hi_a == hi_b) & (lo_a < lo_b))


def equal(hi_a, lo_a, hi_b, lo_b):
    return (hi_a == hi_b) & (lo_a == lo_b)


def canonical(hi, lo, *, k: int):
    """Return (hi, lo, flipped): lexicographic min of the k-mer and its RC."""
    rhi, rlo = reverse_complement(hi, lo, k=k)
    flip = less(rhi, rlo, hi, lo)
    chi = jnp.where(flip, rhi, hi)
    clo = jnp.where(flip, rlo, lo)
    return chi, clo, flip


def _mix32(x):
    """murmur3 fmix32."""
    x = x ^ (x >> 16)
    x = x * U32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * U32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def kmer_hash(hi, lo):
    """32-bit avalanche hash of the dual-lane code."""
    return _mix32(hi ^ _mix32(lo ^ U32(0x9E3779B9)))


def pack_window(bases, *, k: int):
    """Pack a [..., k] uint8 base window into a dual-lane code."""
    hi = jnp.zeros(bases.shape[:-1], dtype=U32)
    lo = jnp.zeros(bases.shape[:-1], dtype=U32)
    for i in range(k):
        hi, lo = append_base(hi, lo, bases[..., i], k=k)
    return hi, lo


def decode(hi, lo, *, k: int):
    """Unpack a dual-lane code into [..., k] uint8 bases."""
    outs = []
    for i in range(k):
        shift = 2 * (k - 1 - i)
        if shift >= 32:
            b = (hi >> (shift - 32)) & 3
        else:
            b = (lo >> shift) & 3
        outs.append(b.astype(jnp.uint8))
    return jnp.stack(outs, axis=-1)


@functools.partial(jax.jit, static_argnames=("k",))
def extract_kmers(bases, lengths, *, k: int):
    """All k-mer windows of a dense read batch.

    Args:
      bases:   [R, L] uint8 (INVALID_BASE past length / for N).
      lengths: [R] int32.
    Returns:
      hi, lo: [R, W] uint32 packed forward-strand codes, W = L - k + 1.
      valid:  [R, W] bool (window inside read, no invalid bases).
      left / right: [R, W] uint8 extension base before/after the window
                    (INVALID_BASE when absent).
    """
    R, L = bases.shape
    W = L - k + 1
    assert W >= 1, f"reads shorter than k: L={L} k={k}"
    hi = jnp.zeros((R, W), dtype=U32)
    lo = jnp.zeros((R, W), dtype=U32)
    for i in range(k):
        hi, lo = append_base(hi, lo, bases[:, i : i + W], k=k)
    inv = (bases >= INVALID_BASE).astype(jnp.int32)
    csum = jnp.concatenate([jnp.zeros((R, 1), jnp.int32), jnp.cumsum(inv, axis=1)], axis=1)
    no_invalid = (csum[:, k:] - csum[:, :-k]) == 0  # [R, W]
    pos = jnp.arange(W, dtype=jnp.int32)[None, :]
    inside = pos + k <= lengths[:, None]
    valid = no_invalid & inside
    # Extensions: base just before / just after the window.
    left = jnp.concatenate(
        [jnp.full((R, 1), INVALID_BASE, jnp.uint8), bases[:, : W - 1]], axis=1
    )
    right_src = bases[:, k:]
    right = jnp.concatenate(
        [right_src, jnp.full((R, 1), INVALID_BASE, jnp.uint8)], axis=1
    )
    right = jnp.where(pos + k < lengths[:, None], right, INVALID_BASE)
    left = jnp.where(pos > 0, left, INVALID_BASE)
    return hi, lo, valid, left, right


def embed_tag(hi, lo, tag, *, k: int, tag_bits: int):
    """Pack an integer tag above the 2k code bits (for (contig, mer) keys).

    Requires 2k + tag_bits <= 62 so the tagged key still fits the dual-lane
    convention (hi's top two bits stay clear for the EMPTY sentinel).
    """
    assert 2 * k + tag_bits <= 62, f"tag does not fit: 2*{k}+{tag_bits} > 62"
    t = tag.astype(U32) & U32((1 << tag_bits) - 1)
    shift = 2 * k
    if shift >= 32:
        return hi | (t << (shift - 32)), lo
    new_lo = lo | (t << shift)
    # bits of the tag that spill past lane 0
    spill = t >> (32 - shift)
    return hi | spill, new_lo


#: XLA:TPU tiles an array's minor dimension by 128.  Flattening [R, L]
#: rows with L off that tiling is a relayout whose TPU compile takes
#: minutes at read-set scale (a 50,000 x 150 uint32 array: ~2 min on a
#: described v5e), and seconds once the rows are padded to the tiling.
LANE_TILE = 128


def flat_lanes(x, fill):
    """[R, L] -> [R * Lp] row-major, with Lp = L rounded up to LANE_TILE
    and the padding columns set to `fill` (callers pass a value that reads
    as invalid)."""
    pad = (-x.shape[1]) % LANE_TILE
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)), constant_values=fill)
    return x.reshape((-1,))


def complement_base(b):
    """3 - b for real bases; INVALID stays invalid."""
    return jnp.where(b < 4, (3 - b).astype(b.dtype), b)


def canonicalize_occurrences(hi, lo, left, right, *, k: int):
    """Canonical form of k-mer occurrences, swapping/complementing extensions.

    When the canonical form is the RC, the left extension of the forward
    form becomes the (complemented) right extension of the canonical form
    and vice versa.
    """
    chi, clo, flip = canonical(hi, lo, k=k)
    cleft = jnp.where(flip, complement_base(right), left)
    cright = jnp.where(flip, complement_base(left), right)
    return chi, clo, cleft, cright, flip
