"""Pallas TPU kernel: Mamba-2 SSD chunked scan (zamba2 / long-context cells).

The long_500k decode/scan cells are recurrence-bound.  The SSD trick
(Mamba-2, arXiv:2405.21060) splits the sequence into chunks: within a chunk
the recurrence unrolls into dense matmuls (MXU work); across chunks only an
[H, P, N] state carry survives.  Grid = (batch, chunks) with the chunk axis
declared sequential ("arbitrary") so the state scratch carries across grid
steps — the TPU-native version of the paper-adjacent segmented-scan
machinery (the same segment-reduction shape as contig run-length counting,
see DESIGN.md §4).

Scalar-per-head decay (A = exp(a)), as used by Mamba-2 and Zamba-2.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_CHUNK = 128


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *, chunk: int):
    cj = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)   # [T, H, P]
    a = a_ref[0].astype(jnp.float32)   # [T, H] decay logits
    b = b_ref[0].astype(jnp.float32)   # [T, H, N]
    c = c_ref[0].astype(jnp.float32)   # [T, H, N]
    T, H, P = x.shape
    N = b.shape[-1]

    @pl.when(cj == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    state = state_ref[...]  # [H, P, N] carry
    # cumulative decay within the chunk: L[t] = prod_{u<=t} A[u]
    loga = a  # log A
    cum = jnp.cumsum(loga, axis=0)  # [T, H]
    # contribution of the carried-in state: y_state[t] = (prod_{u<=t} A) * C[t] . state
    decay_in = jnp.exp(cum)  # [T, H]
    y_state = jnp.einsum("hpn,thn->thp", state, c) * decay_in[:, :, None]
    # intra-chunk causal mix: y_intra[t] = sum_{s<=t} (prod_{s<u<=t} A) (C[t].B[s]) x[s]
    # weights W[t, s] = exp(cum[t] - cum[s]) for s <= t
    w = jnp.exp(cum[:, None, :] - cum[None, :, :])  # [T, S, H]
    tri = jnp.tril(jnp.ones((T, T), jnp.float32))
    cb = jnp.einsum("thn,shn->tsh", c, b)  # [T, S, H]
    mix = cb * w * tri[:, :, None]
    y_intra = jnp.einsum("tsh,shp->thp", mix, x)
    y_ref[0] = (y_state + y_intra).astype(y_ref.dtype)
    # carry state to the next chunk:
    # state' = (prod_chunk A) * state + sum_s (prod_{s<u<T} A) x[s] B[s]^T
    total = jnp.exp(cum[-1])  # [H]
    tail = jnp.exp(cum[-1][None, :] - cum)  # [T, H]
    upd = jnp.einsum("thp,thn->hpn", x * tail[:, :, None], b)
    state_ref[...] = state * total[:, None, None] + upd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, a, b, c, *, chunk: int = DEFAULT_CHUNK, interpret: bool = True):
    """Chunked SSD scan.  x: [B, S, H, P]; a: [B, S, H]; b, c: [B, S, H, N].

    Returns y: [B, S, H, P].  S must be divisible by `chunk`.
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    assert S % chunk == 0, (S, chunk)
    grid = (B, S // chunk)
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, H, P), lambda bi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, chunk, H), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, H, N), lambda bi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, chunk, H, N), lambda bi, ci: (bi, ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, H, P), lambda bi, ci: (bi, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, P), x.dtype),
        scratch_shapes=[pltpu_scratch(H, P, N)],
        interpret=interpret,
        compiler_params=_seq_grid_params(),
    )(x, a, b, c)


def pltpu_scratch(H, P, N):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM((H, P, N), jnp.float32)


def _seq_grid_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
