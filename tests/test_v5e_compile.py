"""The main-path jnp ops compile for a TPU v5e chip, at pipeline widths.

`kernels.ops` serves the `ref` backend on every platform, so these are the
programs a chip runs.  Each case lowers one op against a described (not
attached) v5e chip and hands it to the TPU compiler, which refuses what
the chip cannot run: an unsupported op, or a program larger than the
chip's 16 GB of HBM.  Nothing executes; the shapes are pipeline widths
(150 bp reads, k = 21, tables of 2^20 slots), cut in rows to keep each
compile to seconds.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and test workers each import this
file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import alignment, dbg, dht, kmer_analysis
from repro.core.types import KmerSet
from repro.kernels import ref

HBM_BYTES = 16 * 10**9   # one v5e chip
R, L, K = 64, 150, 21    # reads x read length, k
SLOTS = 1 << 20          # seed index / walk / probe table slots
KMERS = 1 << 16          # count table and k-mer set rows
CONTIGS, CONTIG_LEN = 4096, 32768
MER_SIZES = (17, 21, 25)

u8, u32, i32, b = jnp.uint8, jnp.uint32, jnp.int32, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU library stays loaded in this worker; without this flag it
    # installs crash handlers that print a stack trace into the shared
    # test log when the worker is stopped with SIGTERM
    flags = os.environ.get("LIBTPU_INIT_ARGS", "")
    os.environ["LIBTPU_INIT_ARGS"] = f"{flags} --install_signal_handlers=false"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described v5e chip.

    The persistent compilation cache stays off meanwhile: a program
    compiled for a described chip is written to it but cannot be read back
    without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _table(S, n):
    return (S((n,), u32), S((n,), u32), S((n,), b), S((), i32))


def _kmer_extract(S):
    return (functools.partial(ref.kmer_extract_ref, k=K),
            (S((R, L), u8), S((R,), i32)))


def _count_fold(S):
    n = R * L  # the lanes keep the read width
    fn = functools.partial(kmer_analysis.count_occurrences, capacity=KMERS)
    return fn, (S((n,), u32), S((n,), u32), S((n,), u8), S((n,), u8),
                S((n,), b))


def _dht_insert(S):
    n = R * L  # the lanes keep the read width
    return ref.dht_insert_ref, _table(S, SLOTS) + (S((n,), u32), S((n,), u32),
                                                   S((n,), b))


def _dht_lookup(S):
    n = SLOTS // 2
    return ref.dht_lookup_ref, _table(S, SLOTS) + (S((n,), u32), S((n,), u32),
                                                   S((n,), b))


def _seed_probe(S):
    pos = tuple(alignment._seed_positions(L, K, 16))
    fn = functools.partial(ref.seed_probe_ref, seed_len=K, positions=pos)
    return fn, ((S((R, L), u8), S((R,), i32)) + _table(S, SLOTS)
                + (S((SLOTS,), i32), S((SLOTS,), i32), S((SLOTS,), b),
                   S((SLOTS,), b)))


def _mer_walk(S):
    E = 2 * CONTIGS
    rungs = lambda shape, dtype: tuple(S(shape, dtype) for _ in MER_SIZES)
    fn = functools.partial(ref.mer_walk_ref, mer_sizes=MER_SIZES,
                           tag_bits=12, max_ext=64, seed_len=K)
    return fn, (S((E,), u32), S((E,), u32), S((E,), i32), S((E,), b),
                S((E,), u32), S((E,), u32),
                rungs((SLOTS,), u32), rungs((SLOTS,), u32), rungs((SLOTS,), b),
                rungs((), i32), rungs((SLOTS, 4), i32), rungs((SLOTS, 4), i32))


def _traverse(S):
    n = KMERS
    kset = KmerSet(hi=S((n,), u32), lo=S((n,), u32), count=S((n,), i32),
                   left_cnt=S((n, 4), i32), right_cnt=S((n, 4), i32),
                   left_ext=S((n,), u8), right_ext=S((n,), u8),
                   used=S((n,), b))
    index = dbg.KmerIndex(table=dht.HashTable(*_table(S, 2 * n)),
                          slot_to_row=S((2 * n,), i32))
    fn = functools.partial(dbg.traverse, k=K, contig_cap=CONTIGS,
                           max_len=CONTIG_LEN)
    return fn, (kset, index)


CASES = {
    "kmer_extract": _kmer_extract,
    "count_fold": _count_fold,
    "dht_insert": _dht_insert,
    "dht_lookup": _dht_lookup,
    "seed_probe": _seed_probe,
    "mer_walk": _mer_walk,
    "traverse": _traverse,
}


@pytest.mark.parametrize("name", list(CASES))
def test_ref_op_compiles_for_v5e(name, spec):
    fn, args = CASES[name](spec)
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, (name, used)
