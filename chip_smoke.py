"""Run the whole assembly once on a TPU and check it against ground truth.

    python chip_smoke.py              # one chip: Assembler(plan, Local())
    python chip_smoke.py --chips 4    # Mesh(4) against Local() on 4 chips

Generates an MGSim community (paper §IV-A: log-normal abundances,
WGSim-style 2x150 bp pairs with substitution errors) from --seed: one
genome of 20-30 kbp per 1,250 pairs, so 20 genomes (~0.5 Mbp) at the
default 25k pairs, ~15x mean coverage at any --pairs.  It sizes the plan
with `AssemblyPlan.from_dataset`, and drives
`Assembler(plan, ctx).assemble(reads)` through k-mer analysis, contig
rounds, alignment, local assembly, scaffolding and gap closing.  The
scaffolds are then checked with benchmarks/metrics.py against the known
genomes:

  * every overflow counter is 0 (context accounting and every round's
    k-mer table / traversal flag);
  * genome fraction >= 0.9 on every genome expected at >= 10x coverage;
  * misassemblies <= 1% of pieces.

With --chips 4 the script runs only `Mesh(num_shards=4)` and the `Local()`
run it is compared with, at the tolerance of
tests/test_mesh_vs_local.py::test_mesh_assemble_matches_local.

Per-stage wall times are printed for information: one unrepeated run, with
the compile time inside each stage (trace + lower + XLA compile, or the
persistent-cache read) shown beside it as set-up.  They claim no speed.

Everything runs in this one process; no JAX child is started.  Without a
TPU, or when any check fails, the script exits non-zero and prints no
result.  The last line of a passing run is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# 2x150 bp HiSeq-style pairs, 270 +- 30 bp inserts (CAMI-like read shape)
READ_LEN = 150
INSERT_MEAN = 270
INSERT_SD = 30
ERR_RATE = 0.005
ABUNDANCE_SIGMA = 0.5
# genomes stay under the default plan's 2^15-base contig cap: a genome at
# >= 12x assembles into one contig, and a longer one would overflow it.
# At ~15x the traversal yields ~1.8 contigs per kbp (4.4 at 24x: error
# k-mers seen twice grow hair), so ~2 Mbp stays under contig_cap = 4096
GENOME_LEN = (20_000, 30_000)
PAIRS_PER_GENOME = 1_250
K_RANGE = (17, 21, 4)
UNIQUE_RATE = 0.1
MIN_PIECE = 500        # metaQUAST's default minimum contig length
MIN_COVERAGE = 10.0    # genome-fraction check covers these genomes only
MIN_GENOME_FRACTION = 0.9
MAX_MISASSEMBLED_SHARE = 0.01


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, plus cache hits."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_dataset(args):
    """MGSim community + reads from the seed; per-genome expected coverage."""
    import numpy as np

    from repro.data import mgsim

    comm = mgsim.sample_community(
        args.seed, num_genomes=max(1, args.pairs // PAIRS_PER_GENOME),
        genome_len=GENOME_LEN, abundance_sigma=ABUNDANCE_SIGMA,
    )
    reads, _ = mgsim.generate_reads(
        args.seed + 1, comm, num_pairs=args.pairs, read_len=READ_LEN,
        insert_mean=INSERT_MEAN, insert_sd=INSERT_SD, err_rate=ERR_RATE,
    )
    lens = np.array([len(g) for g in comm.genomes])
    coverage = args.pairs * comm.abundances * 2 * READ_LEN / lens
    return comm, reads, coverage


def run_assembly(label, plan, ctx, reads, clock):
    """Drive `Assembler.assemble_iter`, timing the span up to each event."""
    import jax

    from repro.api import Assembler

    rows = []
    gen = Assembler(plan, ctx).assemble_iter(reads)
    t0, c0 = time.perf_counter(), clock.seconds
    while True:
        try:
            stage, info = next(gen)
            name = f"{stage} k={info['k']}" if "k" in info else stage
        except StopIteration as stop:
            out = jax.block_until_ready(stop.value)
            name = "close_gaps"
        t1, c1 = time.perf_counter(), clock.seconds
        rows.append((name, t1 - t0, c1 - c0))
        print(f"[{label}] stage {name}: wall {t1 - t0:.3f} s "
              f"(compile {c1 - c0:.3f} s)", flush=True)
        t0, c0 = t1, c1
        if name == "close_gaps":
            break
    total = sum(r[1] for r in rows)
    compile_s = sum(r[2] for r in rows)
    print(f"[{label}] assemble: wall {total:.3f} s, of which compile "
          f"{compile_s:.3f} s (set-up); one unrepeated run", flush=True)
    return out


def quality(out, comm, coverage):
    """metaQUAST-style report of the scaffolds against the true genomes."""
    import numpy as np

    from benchmarks import metrics

    seqs = out["scaffold_seqs"]
    lens = np.asarray(seqs.lengths)
    bases = np.asarray(seqs.bases)
    pieces = [bases[i, : lens[i]] for i in range(len(lens))
              if lens[i] >= MIN_PIECE]
    rep = metrics.evaluate(pieces, comm.genomes)
    fr = np.asarray(rep["genome_fractions"])
    deep = coverage >= MIN_COVERAGE
    rep["deep_genomes"] = int(deep.sum())
    rep["deep_genome_fraction_min"] = float(fr[deep].min()) if deep.any() \
        else 0.0
    return rep


def overflow_counts(out) -> dict:
    """Every overflow signal of a run, as counts (0 = nothing dropped).

    Besides the context's accounting and each round's k-mer table and
    traversal flags (contig count or length past the plan's caps), a
    scaffold that reached `max_scaffold_len` was cut there."""
    counts = dict(out["overflow"])
    for st in out["stats"]:
        counts[f"round_k{st.k}"] = int(st.overflow)
        counts[f"round_k{st.k}_route"] = int(st.route_overflow)
    cut = out["scaffold_seqs"].lengths >= out["plan"].max_scaffold_len
    counts["scaffold_len"] = int(cut.sum())
    return counts


def report(label, out, rep, coverage):
    import numpy as np

    print(f"[{label}] overflow: {overflow_counts(out)}")
    for st in out["stats"]:
        print(f"[{label}] k={st.k}: {st.n_kmers} k-mers -> {st.n_contigs} "
              f"contigs (bubbles {st.n_bubbles}, hair {st.n_hair}, pruned "
              f"{st.n_pruned}); aligned {st.aligned_frac:.4f}; local "
              f"assembly +{st.extended_bases} bp")
    cov = np.round(coverage, 1).tolist()
    fr = np.round(rep["genome_fractions"], 4).tolist()
    print(f"[{label}] per genome (expected coverage, genome fraction): "
          f"{list(zip(cov, fr))}")
    print(f"[{label}] pieces >= {MIN_PIECE} bp: {rep['n_pieces']}, total "
          f"{rep['total_len']} bp, N50 {rep['n50']}, misassemblies "
          f"{rep['misassemblies']}, genome fraction {rep['genome_fraction']:.4f}"
          f" (min over {rep['deep_genomes']} genomes >= {MIN_COVERAGE:g}x: "
          f"{rep['deep_genome_fraction_min']:.4f})", flush=True)


def check_local(out, rep) -> list:
    failures = []
    ovf = {k: v for k, v in overflow_counts(out).items() if v}
    if ovf:
        failures.append(f"overflow counters not 0: {ovf}")
    if rep["deep_genomes"] == 0:
        failures.append(f"no genome expected at >= {MIN_COVERAGE:g}x")
    elif rep["deep_genome_fraction_min"] < MIN_GENOME_FRACTION:
        failures.append(
            f"genome fraction {rep['deep_genome_fraction_min']:.4f} < "
            f"{MIN_GENOME_FRACTION} on a genome expected at >= "
            f"{MIN_COVERAGE:g}x")
    if rep["n_pieces"] == 0:
        failures.append("no scaffold reached the minimum piece length")
    elif rep["misassemblies"] > MAX_MISASSEMBLED_SHARE * rep["n_pieces"]:
        failures.append(f"{rep['misassemblies']} misassemblies in "
                        f"{rep['n_pieces']} pieces (> "
                        f"{MAX_MISASSEMBLED_SHARE:.0%})")
    return failures


def check_mesh(rep_l, out_m, rep_m) -> list:
    """tests/test_mesh_vs_local.py::test_mesh_assemble_matches_local."""
    failures = []
    if rep_m["genome_fraction"] < rep_l["genome_fraction"] - 0.02:
        failures.append(f"mesh genome fraction {rep_m['genome_fraction']:.4f}"
                        f" < local {rep_l['genome_fraction']:.4f} - 0.02")
    if rep_m["misassemblies"] > rep_l["misassemblies"] + 1:
        failures.append(f"mesh misassemblies {rep_m['misassemblies']} > "
                        f"local {rep_l['misassemblies']} + 1")
    ovf = {k: v for k, v in overflow_counts(out_m).items()
           if ("route" in k or "localize" in k) and v}
    if ovf:
        failures.append(f"mesh route/localize overflow: {ovf}")
    return failures


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def shard_layout(out) -> list:
    """(device id, read rows) of every shard of the final alignment, the
    output of a read-proportional shard_map stage."""
    return sorted((s.device.id, s.data.shape[0])
                  for s in out["alignments"].contig.addressable_shards)


def smoke_local(args, clock) -> list:
    import jax

    from repro.api import AssemblyPlan, Local

    t0 = time.perf_counter()
    comm, reads, coverage = make_dataset(args)
    print(f"dataset: {len(comm.genomes)} genomes, "
          f"{sum(len(g) for g in comm.genomes)} bp, {args.pairs} pairs, "
          f"reads {reads.num_reads} x {reads.max_len} bp, insert "
          f"{INSERT_MEAN}+-{INSERT_SD}, error rate {ERR_RATE}; generated in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    plan = AssemblyPlan.from_dataset(reads, K_RANGE, unique_rate=UNIQUE_RATE)
    print(f"plan: k {K_RANGE}, kmer_capacity {plan.kmer_capacity}, contig_cap "
          f"{plan.contig_cap}, max_contig_len {plan.max_contig_len}, "
          f"walk_capacity {plan.walk_capacity}, plan.bytes() {plan.bytes()}",
          flush=True)
    out = run_assembly("local", plan, Local(), reads, clock)
    print(f"peak_bytes_in_use: {peak_bytes(jax.devices()[0])}")
    rep = quality(out, comm, coverage)
    report("local", out, rep, coverage)
    return check_local(out, rep)


def smoke_mesh(args, clock, n) -> list:
    import jax

    from repro.api import AssemblyPlan, Local, Mesh

    comm, reads, coverage = make_dataset(args)
    print(f"dataset: {len(comm.genomes)} genomes, {args.pairs} pairs, reads "
          f"{reads.num_reads} x {reads.max_len} bp", flush=True)
    plan = AssemblyPlan.from_dataset(reads, K_RANGE, num_shards=n,
                                     unique_rate=UNIQUE_RATE)
    print(f"plan (per shard, {n} shards): plan.bytes() {plan.bytes()}",
          flush=True)
    out_l = run_assembly("local", plan, Local(), reads, clock)
    rep_l = quality(out_l, comm, coverage)
    report("local", out_l, rep_l, coverage)
    del out_l  # free the Local run's device arrays before the mesh run
    ctx = Mesh(num_shards=n)
    out_m = run_assembly(f"mesh{n}", plan, ctx, reads, clock)
    rep_m = quality(out_m, comm, coverage)
    report(f"mesh{n}", out_m, rep_m, coverage)
    layout = shard_layout(out_m)
    peaks = [peak_bytes(d) for d in jax.devices()[:n]]
    print(f"[mesh{n}] read shards (device id, rows): {layout}")
    print(f"[mesh{n}] peak_bytes_in_use per device: {peaks}", flush=True)
    failures = check_mesh(rep_l, out_m, rep_m)
    if sorted(d for d, _ in layout) != sorted(
            d.id for d in jax.devices()[:n]):
        failures.append(f"read shards not on all {n} devices: {layout}")
    if len({rows for _, rows in layout}) != 1:
        failures.append(f"read shards uneven: {layout}")
    if any(p is not None and p <= 0 for p in peaks):
        failures.append(f"a device held no memory: {peaks}")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs Mesh(4) against Local() and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    # 25k pairs: a cold run (every program compiled) takes ~8 min on one
    # v5e; 100k pairs did not finish in 20 min
    ap.add_argument("--pairs", type=int, default=25_000,
                    help="read pairs; the community grows with them")
    args = ap.parse_args(argv)

    info = device_info()
    print(f"device: platform {info['platform']}, kind {info['kind']}, "
          f"count {info['count']}", flush=True)
    if info["platform"] != "tpu":
        print("error: JAX finds no TPU; this smoke has no CPU fallback",
              file=sys.stderr)
        return 1
    if info["count"] < args.chips:
        print(f"error: --chips {args.chips} but JAX sees {info['count']}",
              file=sys.stderr)
        return 1
    sys.path[:0] = [os.path.join(REPO, "src"), REPO]
    from repro import compile_cache

    print(f"compile cache: {compile_cache.enable()}", flush=True)
    clock = CompileClock()
    if args.chips == 1:
        failures = smoke_local(args, clock)
    else:
        failures = smoke_mesh(args, clock, args.chips)
    print(f"compile total {clock.seconds:.3f} s; persistent cache hits "
          f"{clock.cache_hits}, misses {clock.cache_misses}")
    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
