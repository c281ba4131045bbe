"""Execution contexts: where the pipeline's read-proportional work runs.

The paper's defining property is that one pipeline "runs seamlessly on
shared and distributed-memory systems"; here that is an explicit seam.
`Assembler` drives Algorithm 1 + Algorithm 3 against a small *stage
protocol* (`ExecutionContext`), and the two implementations place the work
differently:

  * `Local()` — every stage on the current default device, numerically
    identical to the historical `core.pipeline.assemble`;
  * `Mesh(num_shards)` — read-proportional stages (k-mer analysis,
    alignment, local assembly, link-witness generation) run per shard on a
    1-D "data" mesh with the paper's owner exchanges between them
    (DESIGN.md §6); contig-proportional stages (traversal, matching)
    replicate, because contig state is orders of magnitude smaller than
    read state.

The protocol is deliberately narrow: `prepare`, `kmer_set`, `align`,
`extend`, `link_candidates`, plus `overflow()` accounting.  Everything a
context returns is in *global* layout (full-length arrays), so the
Assembler never branches on the execution strategy.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import alignment, kmer_analysis, local_assembly, scaffolding


def _merge_contigs(tab, prev, k: int, plan):
    """Fold the previous round's contig k-mers into a count table as
    pseudo-counted evidence (§II-H)."""
    contigs, alive = prev
    return kmer_analysis.merge_contig_kmers(
        tab, contigs.bases, jnp.where(alive, contigs.lengths, 0), k=k,
        capacity=plan.kmer_capacity, weight=plan.contig_pseudo_weight,
        backend=plan.kernel_backend,
    )


class ExecutionContext:
    """Stage protocol shared by Local and Mesh execution."""

    num_shards: int = 1

    def prepare(self, reads, plan) -> None:
        """Bind the dataset + plan; called once per `assemble`."""
        raise NotImplementedError

    def kmer_set(self, k: int, prev):
        """Counted, finalized k-mer set for this round.

        `prev` is None, a (contigs, alive) pair from the previous round
        (whose (k)-mers enter as pseudo-counted evidence, §II-H), or a
        precomputed count table dict (legacy shim path).
        Returns (KmerSet, overflow_dict).
        """
        raise NotImplementedError

    def align(self, contigs, alive, k: int):
        """Alignments of every read against the live contigs ([R, 2])."""
        raise NotImplementedError

    def extend(self, contigs, alive, al, k: int):
        """Local-assembly extension of contig ends (§II-G)."""
        raise NotImplementedError

    def link_candidates(self, al, contigs, alive):
        """Per-read splint/span link witnesses (flat candidate arrays)."""
        raise NotImplementedError

    # ---- streaming protocol (DESIGN.md §7) ----

    def prepare_stream(self, plan, *, checkpoint_dir=None) -> None:
        """Bind a plan for out-of-core execution (no resident read set)."""
        raise NotImplementedError

    def stream_kmer_set(self, k: int, batches, prev):
        """Two-pass streamed k-mer set over a re-iterable batch source.

        Same contract as `kmer_set` plus the per-stream accounting:
        returns (KmerSet, overflow_dict, StreamStats).
        """
        raise NotImplementedError

    def align_batch(self, batch, contigs, sidx, seed_len: int):
        """Alignments of one batch against the (replicated) seed index."""
        raise NotImplementedError

    def _kmer_ckpt_dir(self, k: int):
        base = getattr(self, "_stream_ckpt", None)
        if base is None:
            return None
        import os

        return os.path.join(base, f"k{k}")

    def spawn(self) -> "ExecutionContext":
        """A fresh context of the same kind sharing the expensive device
        resources (the jax mesh, for Mesh) but NONE of the per-run state
        (bound plan, checkpoint dir, overflow counters).  The job server
        multiplexes many runs onto one set of devices; each run must get
        its own spawn or interleaved runs would clobber each other's
        bindings."""
        raise NotImplementedError

    def overflow(self) -> dict:
        """Accumulated overflow counts (reported, never dropped: §3.4)."""
        return dict(self._overflow)

    def _note_overflow(self, key: str, n) -> None:
        self._overflow[key] = self._overflow.get(key, 0) + int(n)

    def _reset_overflow(self) -> None:
        self._overflow = {}


class Local(ExecutionContext):
    """Single-shard execution on the default device.

    Numerically identical to the pre-facade `core.pipeline` stages — the
    backward-compat shims delegate here and tests assert scaffold
    equality.
    """

    def __init__(self):
        self._reset_overflow()

    def spawn(self) -> "Local":
        return Local()

    def prepare(self, reads, plan) -> None:
        self.reads = reads
        self.plan = plan
        self._reset_overflow()

    def kmer_set(self, k: int, prev):
        plan = self.plan
        hi, lo, left, right, valid = kmer_analysis.occurrences(
            self.reads, k=k, backend=plan.kernel_backend
        )
        if plan.low_memory:
            valid = kmer_analysis.admit_two_sightings(
                hi, lo, valid, bloom_bits=max(1 << 16, plan.kmer_capacity * 8)
            )
        tab = kmer_analysis.count_occurrences(
            hi, lo, left, right, valid, capacity=plan.kmer_capacity
        )
        if isinstance(prev, dict):
            tab = kmer_analysis.merge_counts(
                tab, prev, capacity=plan.kmer_capacity
            )
        elif prev is not None:
            tab = _merge_contigs(tab, prev, k, plan)
        self._note_overflow("kmer_table", tab["overflow"])
        kset = kmer_analysis.finalize(
            tab, min_count=plan.min_count, policy=plan.policy
        )
        return kset, {"table": bool(tab["overflow"])}

    def align(self, contigs, alive, k: int):
        seed_len = min(k, 27)
        sidx = alignment.build_seed_index(
            contigs, alive, seed_len=seed_len, capacity=self.plan.seed_cap,
            backend=self.plan.kernel_backend,
        )
        return alignment.align_reads(
            self.reads, contigs, sidx, seed_len=seed_len,
            stride=self.plan.seed_stride,
            gapped=self.plan.gapped_align,
            backend=self.plan.kernel_backend,
        )

    def extend(self, contigs, alive, al, k: int):
        extended, _walk = local_assembly.extend_contigs(
            self.reads, contigs, alive, al.contig[:, 0],
            mer_sizes=self.plan.ladder(k),
            capacity=self.plan.walk_capacity,
            max_ext=self.plan.max_ext,
            backend=self.plan.kernel_backend,
        )
        return extended

    def link_candidates(self, al, contigs, alive):
        clens = jnp.where(alive, contigs.lengths, 0)
        return scaffolding.candidate_links(al, self.reads, clens)

    # ---- streaming (DESIGN.md §7) ----

    def prepare_stream(self, plan, *, checkpoint_dir=None) -> None:
        self.plan = plan
        self._stream_ckpt = checkpoint_dir
        self._reset_overflow()

    def stream_kmer_set(self, k: int, batches, prev):
        from repro.stream import analysis as stream_analysis

        plan = self.plan
        run, sstats = stream_analysis.streaming_kmer_analysis(
            batches, k=k, capacity=plan.kmer_capacity,
            bloom_bits=plan.bloom_slots,
            checkpoint_dir=self._kmer_ckpt_dir(k),
            backend=plan.kernel_backend,
        )
        if prev is not None:
            run = _merge_contigs(run, prev, k, plan)
            sstats.table_overflow += int(run["overflow"])
        self._note_overflow("kmer_table", sstats.table_overflow)
        kset = kmer_analysis.finalize(
            run, min_count=self.plan.min_count, policy=self.plan.policy
        )
        return kset, {"table": bool(sstats.table_overflow)}, sstats

    def align_batch(self, batch, contigs, sidx, seed_len: int):
        return alignment.align_reads(
            batch, contigs, sidx, seed_len=seed_len,
            stride=self.plan.seed_stride,
            gapped=self.plan.gapped_align,
            backend=self.plan.kernel_backend,
        )


class Mesh(ExecutionContext):
    """Distributed execution over a 1-D "data" mesh (DESIGN.md §3, §6).

    Read-proportional stages run per shard via `repro.dist`; k-mer and
    link state move through the paper's owner exchanges; contig-scale
    graph work replicates.  Requires `num_shards` visible devices (host
    devices count: set XLA_FLAGS=--xla_force_host_platform_device_count
    before importing jax).
    """

    def __init__(self, num_shards: int = 8, *, mesh=None):
        if num_shards < 1:
            raise ValueError(f"Mesh needs num_shards >= 1, got {num_shards}")
        self.num_shards = int(num_shards)
        self._mesh = mesh
        self._reset_overflow()

    @property
    def mesh(self):
        if self._mesh is None:
            from repro.dist import pipeline as dist

            self._mesh = dist.data_mesh(self.num_shards)
        return self._mesh

    def spawn(self) -> "Mesh":
        # share the built jax device mesh (the expensive part); per-run
        # bindings (plan, sharded reads, checkpoints, overflow) start fresh
        return Mesh(num_shards=self.num_shards, mesh=self.mesh)

    def _adapt_plan(self, plan, constructor: str):
        """Validate/re-derive a plan for this mesh width (shared by the
        in-memory and streaming prepare paths).

        A default (single-shard) plan adapts: the global capacities carry
        over, the per-shard ones (pre_cap, route_cap, ...) re-derive for
        this mesh width so exchange buffers and plan.bytes() are priced
        for S shards, not 1."""
        import dataclasses

        if plan.num_shards not in (1, self.num_shards):
            raise ValueError(
                f"plan was sized for {plan.num_shards} shards but the mesh "
                f"has {self.num_shards}; re-plan with "
                f"AssemblyPlan.{constructor}(..., num_shards="
                f"{self.num_shards})"
            )
        if plan.num_shards != self.num_shards:
            plan = dataclasses.replace(plan, num_shards=self.num_shards)
        return plan

    def prepare(self, reads, plan) -> None:
        from repro.dist import pipeline as dist

        self.reads = reads          # original layout: scaffolding mates
        self.plan = self._adapt_plan(plan, "from_dataset")
        self.sharded = dist.shard_reads(reads, self.num_shards)
        self._reset_overflow()

    def kmer_set(self, k: int, prev):
        from repro.dist import pipeline as dist, stages

        plan = self.plan
        prev_contigs = None
        if isinstance(prev, dict):
            # a precomputed count table has no shard layout to exchange;
            # refusing beats silently dropping the §II-H evidence
            raise NotImplementedError(
                "Mesh.kmer_set needs (contigs, alive) for the contig-kmer "
                "owner exchange; a precomputed table dict is Local-only "
                "(legacy shim path)"
            )
        if prev is not None:
            prev_contigs = prev
        # route_capacity: pass the explicit override if the plan has one,
        # else let the stage derive it per round — contig-carrying rounds
        # need wider lanes than the first round
        kset_sh, route_ovf, table_ovf = stages.sharded_kmer_analysis(
            self.sharded, self.mesh, k=k,
            pre_capacity=plan.pre_cap,
            capacity=plan.shard_table_cap,
            route_capacity=plan.route_capacity,
            min_count=plan.min_count, policy=plan.policy,
            prev_contigs=prev_contigs,
            contig_weight=plan.contig_pseudo_weight,
            backend=plan.kernel_backend,
        )
        self._note_overflow("kmer_route", route_ovf)
        self._note_overflow("kmer_table", table_ovf)
        merged = dist.gather_ksets(kset_sh, capacity=plan.kmer_capacity)
        self._note_overflow("kmer_gather", merged["overflow"])
        # per-shard finalize already applied the globally-correct min_count
        # (ownership is total); re-finalizing the gathered table recomputes
        # extensions from the summed histograms
        kset = kmer_analysis.finalize(
            merged, min_count=plan.min_count, policy=plan.policy
        )
        return kset, {
            "table": bool(table_ovf) or bool(merged["overflow"]),
            "route": int(route_ovf),
        }

    def align(self, contigs, alive, k: int):
        from repro.dist import stages

        seed_len = min(k, 27)
        sidx = alignment.build_seed_index(
            contigs, alive, seed_len=seed_len, capacity=self.plan.seed_cap,
            backend=self.plan.kernel_backend,
        )
        return stages.sharded_align(
            self.sharded, contigs, sidx, self.mesh,
            seed_len=seed_len, stride=self.plan.seed_stride,
            gapped=self.plan.gapped_align,
            backend=self.plan.kernel_backend,
        )

    def extend(self, contigs, alive, al, k: int):
        from repro.dist import stages

        extended, ovf = stages.sharded_extend(
            self.sharded, contigs, alive, al, self.mesh,
            mer_sizes=self.plan.ladder(k),
            capacity=self.plan.walk_capacity,
            max_ext=self.plan.max_ext,
            out_factor=self.plan.localize_out_factor,
            backend=self.plan.kernel_backend,
        )
        self._note_overflow("localize", ovf)
        return extended

    def link_candidates(self, al, contigs, alive):
        from repro.dist import stages

        cands, ovf = stages.sharded_link_candidates(
            self.sharded, al, contigs, alive, self.mesh,
            out_factor=self.plan.localize_out_factor,
        )
        self._note_overflow("localize_pairs", ovf)
        return cands

    # ---- streaming (DESIGN.md §7) ----

    def prepare_stream(self, plan, *, checkpoint_dir=None) -> None:
        self.plan = self._adapt_plan(plan, "from_stream")
        self._stream_ckpt = checkpoint_dir
        self._reset_overflow()

    def stream_kmer_set(self, k: int, batches, prev):
        from repro.stream import analysis as stream_analysis

        plan = self.plan
        run, sstats = stream_analysis.sharded_streaming_kmer_analysis(
            batches, self.mesh, k=k,
            capacity=plan.shard_table_cap,
            bloom_bits=plan.bloom_slots,
            pre_capacity=plan.pre_cap,
            route_capacity=plan.route_capacity,
            checkpoint_dir=self._kmer_ckpt_dir(k),
            backend=plan.kernel_backend,
        )
        # ownership is total, so the per-owner slices merge into one
        # key-sorted global table by pure re-sort (cf. gather_ksets) —
        # BEFORE any finalize, so §II-H contig evidence merges into raw
        # counts exactly like the Local streaming path
        merged = kmer_analysis.aggregate_weighted(
            run["hi"], run["lo"], run["count"],
            run["left_cnt"], run["right_cnt"], run["count"] > 0,
            capacity=plan.kmer_capacity,
        )
        sstats.table_overflow += int(merged["overflow"])
        if prev is not None:
            merged = _merge_contigs(merged, prev, k, plan)
            sstats.table_overflow += int(merged["overflow"])
        self._note_overflow("kmer_table", sstats.table_overflow)
        self._note_overflow("kmer_route", sstats.route_overflow)
        kset = kmer_analysis.finalize(
            merged, min_count=plan.min_count, policy=plan.policy
        )
        return kset, {
            "table": bool(sstats.table_overflow),
            "route": int(sstats.route_overflow),
        }, sstats

    def align_batch(self, batch, contigs, sidx, seed_len: int):
        import jax

        from repro.dist import pipeline as dist, stages

        sharded = dist.shard_reads(batch, self.num_shards)
        al = stages.sharded_align(
            sharded, contigs, sidx, self.mesh,
            seed_len=seed_len, stride=self.plan.seed_stride,
            gapped=self.plan.gapped_align,
            backend=self.plan.kernel_backend,
        )
        B = batch.num_reads
        return jax.tree.map(lambda x: x[:B], al)
