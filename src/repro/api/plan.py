"""AssemblyPlan: one derived capacity plan for the whole pipeline.

Every buffer in this repo is statically shaped (DESIGN.md §1), which used
to mean ~20 scattered guess-a-power-of-two knobs on `PipelineConfig`
(`kmer_capacity`, `contig_cap`, `walk_capacity`, `link_capacity`, ...)
plus a separate `dist.capacity.plan_kmer_budget` for the distributed path.
`AssemblyPlan` absorbs all of them into one object with two entry points:

  * `AssemblyPlan.from_dataset(reads, k_range, slack=...)` derives every
    stage capacity from dataset shape (`num_reads`, `max_len`, k-range)
    the paper's §II-B way — provision from an upfront cardinality
    estimate, report overflow, never grow dynamically;
  * `plan_from(cfg)` maps a legacy `PipelineConfig` onto a plan field by
    field, so `Assembler(plan_from(cfg), Local())` is numerically the old
    `core.pipeline.assemble(reads, cfg)`.

`plan.bytes()` states the memory bill before any array is allocated —
the TPU translation of MetaHipMer's upfront provisioning (Table II) and
the same memory-bounding stance as MEGAHIT's one-CLI memory strategies.

Validation lives here (`validate_assembly_params`) and is shared with the
`PipelineConfig` shim: bad k-ranges, even k, non-positive capacities, and
inverted mer ladders fail fast with actionable errors instead of shape
errors deep in XLA.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core.kmer_analysis import ExtensionPolicy
from repro.dist import capacity as cap_lib


class PlanError(ValueError):
    """A plan/config parameter is invalid (raised before any tracing)."""


def _ladder(k: int, step: int) -> tuple:
    """Mer-size ladder for the dynamic walk (§II-G); shared with the
    legacy `PipelineConfig.ladder`."""
    return (max(11, k - step), k, min(k + step, 27))


def _normalize_k_range(k_range: tuple) -> tuple:
    """(k_min, k_max) -> (k_min, k_max, step); 3-tuples pass through."""
    if len(k_range) == 2:
        return (k_range[0], k_range[1], max(k_range[1] - k_range[0], 1))
    return tuple(k_range)


def _clamp_contig_cap(base: dict, overrides: dict) -> dict:
    """Respect the (contig, mer) tag-space limit of the walk ladder unless
    the caller pinned contig_cap explicitly.  Shared by every plan
    constructor so the clamp rule cannot drift between them."""
    if "contig_cap" not in overrides:
        step = base.get("walk_ladder_step", 4)
        hi_mer = min(base["k_max"] + step, 27)
        base["contig_cap"] = min(
            base["contig_cap"], 1 << min(16, 62 - 2 * hi_mer)
        )
    return base


def validate_assembly_params(
    *,
    k_min: int,
    k_max: int,
    k_step: int,
    min_count: int,
    kmer_capacity: int,
    contig_cap: int,
    max_contig_len: int,
    walk_capacity: int,
    link_capacity: int,
    max_scaffold_len: int,
    max_members: int,
    max_ext: int,
    walk_ladder_step: int,
    seed_stride: int,
    where: str = "AssemblyPlan",
) -> None:
    """Reject invalid parameters with actionable errors (fail fast)."""
    if k_min > k_max:
        raise PlanError(
            f"{where}: k_min={k_min} > k_max={k_max}; the iterative-k "
            f"schedule runs k_min..k_max and must be non-empty"
        )
    if k_step <= 0:
        raise PlanError(f"{where}: k_step={k_step} must be positive")
    ks = list(range(k_min, k_max + 1, k_step))
    for k in ks:
        if k % 2 == 0:
            raise PlanError(
                f"{where}: k={k} is even; even k makes a k-mer equal its "
                f"own reverse complement, breaking canonicalization — use "
                f"odd k (adjust k_min/k_step)"
            )
        if not 3 <= k <= 31:
            raise PlanError(
                f"{where}: k={k} outside the dual-lane packing range "
                f"3..31 (DESIGN.md §2)"
            )
        lo, mid, hi = _ladder(k, walk_ladder_step)
        if not lo < mid < hi:
            raise PlanError(
                f"{where}: walk ladder {(lo, mid, hi)} for k={k} is not "
                f"strictly increasing; the dynamic mer-walk needs a rung "
                f"below and above k (11 < k < 27 with "
                f"walk_ladder_step={walk_ladder_step})"
            )
        # the (contig, mer) walk tables embed the contig id in the spare
        # high bits of the dual-lane key (kmer.embed_tag); the ladder's
        # top rung fixes how many bits are spare
        tag_bits = min(16, 62 - 2 * hi)
        if contig_cap > (1 << tag_bits):
            raise PlanError(
                f"{where}: contig_cap={contig_cap} exceeds the (contig, "
                f"mer) tag space 2**{tag_bits} left by the k={k} walk "
                f"ladder (top rung {hi}); lower contig_cap or "
                f"walk_ladder_step"
            )
    caps = {
        "min_count": min_count,
        "kmer_capacity": kmer_capacity,
        "contig_cap": contig_cap,
        "max_contig_len": max_contig_len,
        "walk_capacity": walk_capacity,
        "link_capacity": link_capacity,
        "max_scaffold_len": max_scaffold_len,
        "max_members": max_members,
        "max_ext": max_ext,
        "seed_stride": seed_stride,
    }
    for name, v in caps.items():
        if int(v) <= 0:
            raise PlanError(
                f"{where}: {name}={v} must be positive — capacities are "
                f"static buffer sizes chosen before data is seen "
                f"(DESIGN.md §3.4)"
            )


@dataclasses.dataclass(frozen=True)
class AssemblyPlan:
    """Per-stage, per-shard capacity plan + algorithm knobs for one run.

    Capacities are global unless suffixed otherwise; the per-shard numbers
    (`pre_capacity`, `shard_table_capacity`, `route_capacity`) only matter
    when executing on a `Mesh` context and default to values derived from
    the global plan and `num_shards`.
    """

    # --- k schedule + thresholds (Alg. 1) ---
    k_min: int = 17
    k_max: int = 21
    k_step: int = 4
    min_count: int = 2
    policy: ExtensionPolicy = ExtensionPolicy()
    contig_pseudo_weight: int = 4
    low_memory: bool = False
    # --- pruning ---
    prune_alpha: float = 0.25
    prune_beta: float = 0.5
    # --- alignment ---
    seed_stride: int = 16
    # gapped_align: verify candidates with the banded Smith-Waterman
    # dispatch (kernels.ops.sw_extend) instead of vectorized Hamming
    # extension.  The default stays Hamming — the pipeline's read model is
    # substitution-only Illumina — but indel-bearing data can opt in
    # without touching call sites.
    gapped_align: bool = False
    # --- kernel backend (DESIGN.md §8) ---
    # "pallas" | "ref" | None (None = the kernels.ops default, "ref" on
    # every platform — overridable process-wide via the REPRO_KERNELS env
    # var).  Selects which implementation serves the fused hot paths in
    # every stage this plan drives; both backends are bit-identical, but
    # the Pallas kernels do not lower for TPU v5e yet.
    kernel_backend: Optional[str] = None
    # --- local assembly ---
    walk_ladder_step: int = 4
    max_ext: int = 64
    run_local_assembly: bool = True
    # --- scaffolding ---
    min_link_support: int = 2
    max_members: int = 32
    # --- capacities (global) ---
    kmer_capacity: int = 1 << 15
    contig_cap: int = 512
    max_contig_len: int = 4096
    seed_capacity: Optional[int] = None   # default: 2 * kmer_capacity
    walk_capacity: int = 1 << 16
    link_capacity: int = 1 << 12
    max_scaffold_len: int = 1 << 13
    # --- distributed execution (Mesh) ---
    num_shards: int = 1
    slack: float = 2.0
    pre_capacity: Optional[int] = None          # per-shard pre-combine rows
    shard_table_capacity: Optional[int] = None  # per-shard owner-table rows
    route_capacity: Optional[int] = None        # per-(sender, dest) rows
    localize_out_factor: int = 2
    # --- streaming execution (DESIGN.md §7) ---
    # batch_reads: rows per streamed batch (None = in-memory plan);
    # bloom_bits: per-shard Bloom filter slots for the two-pass admission
    # (None = derive from kmer_capacity).  Both set by `from_stream`.
    batch_reads: Optional[int] = None
    bloom_bits: Optional[int] = None
    # dataset shape (num_reads, max_len) — recorded by `from_dataset` /
    # `bind` so `bytes()` can price the read-proportional buffers; for a
    # streaming plan this is (batch_reads, max_len): the device never
    # holds more than one batch of read state
    dataset_shape: Optional[tuple] = None

    def __post_init__(self):
        validate_assembly_params(
            k_min=self.k_min, k_max=self.k_max, k_step=self.k_step,
            min_count=self.min_count, kmer_capacity=self.kmer_capacity,
            contig_cap=self.contig_cap, max_contig_len=self.max_contig_len,
            walk_capacity=self.walk_capacity,
            link_capacity=self.link_capacity,
            max_scaffold_len=self.max_scaffold_len,
            max_members=self.max_members, max_ext=self.max_ext,
            walk_ladder_step=self.walk_ladder_step,
            seed_stride=self.seed_stride, where="AssemblyPlan",
        )
        if self.num_shards < 1:
            raise PlanError(f"AssemblyPlan: num_shards={self.num_shards} < 1")
        if self.kernel_backend is not None:
            from repro.kernels import ops as kernel_ops

            if self.kernel_backend not in kernel_ops.BACKENDS:
                raise PlanError(
                    f"AssemblyPlan: kernel_backend={self.kernel_backend!r} "
                    f"unknown; valid: {kernel_ops.BACKENDS} (or None for "
                    f"the default)"
                )
        for name in ("seed_capacity", "pre_capacity",
                     "shard_table_capacity", "route_capacity"):
            v = getattr(self, name)
            if v is not None and int(v) <= 0:
                raise PlanError(
                    f"AssemblyPlan: {name}={v} must be positive (or None "
                    f"to derive it) — capacities are static buffer sizes "
                    f"(DESIGN.md §3.4)"
                )
        if self.localize_out_factor < 1:
            raise PlanError(
                f"AssemblyPlan: localize_out_factor="
                f"{self.localize_out_factor} < 1 would drop reads by "
                f"construction"
            )
        if self.slack <= 0:
            raise PlanError(f"AssemblyPlan: slack={self.slack} must be > 0")
        if self.batch_reads is not None and (
            self.batch_reads < 2 or self.batch_reads % 2
        ):
            raise PlanError(
                f"AssemblyPlan: batch_reads={self.batch_reads} must be even "
                f"and >= 2 — batches hold whole read pairs"
            )
        if self.bloom_bits is not None and (
            self.bloom_bits <= 0 or self.bloom_bits & (self.bloom_bits - 1)
        ):
            raise PlanError(
                f"AssemblyPlan: bloom_bits={self.bloom_bits} must be a "
                f"positive power of two (Bloom positions mask the hash)"
            )

    # ---- schedule helpers (shared with the PipelineConfig shim) ----

    def ks(self) -> list:
        return list(range(self.k_min, self.k_max + 1, self.k_step))

    def ladder(self, k: int) -> tuple:
        return _ladder(k, self.walk_ladder_step)

    # ---- derived per-shard capacities ----

    @property
    def seed_cap(self) -> int:
        return self.seed_capacity or 2 * self.kmer_capacity

    @property
    def pre_cap(self) -> int:
        """Per-shard local pre-combine table rows (Mesh k-mer analysis)."""
        if self.pre_capacity is not None:
            return self.pre_capacity
        return max(1 << 8, cap_lib.next_pow2(-(-self.kmer_capacity
                                               // self.num_shards)) * 2)

    @property
    def shard_table_cap(self) -> int:
        """Per-shard owner-table rows (hash ownership splits ~evenly)."""
        if self.shard_table_capacity is not None:
            return self.shard_table_capacity
        return self.pre_cap

    @property
    def route_cap(self) -> int:
        if self.route_capacity is not None:
            return self.route_capacity
        return cap_lib.default_route_capacity(
            self.pre_cap, self.num_shards, slack=self.slack
        )

    @property
    def bloom_slots(self) -> int:
        """Per-shard Bloom filter slots for the streamed two-pass admission.

        Defaults to 16x the per-shard share of the k-mer table: the filter
        must sketch the RAW distinct population (true k-mers + error
        singletons, typically ~10x the admitted population) at a low
        false-positive rate, and one slot costs 1/48th of a table row.
        """
        if self.bloom_bits is not None:
            return self.bloom_bits
        return cap_lib.next_pow2(
            max(1 << 14, 16 * self.kmer_capacity // self.num_shards)
        )

    # ---- construction ----

    @classmethod
    def from_dataset(
        cls,
        reads,
        k_range: tuple = (17, 21, 4),
        *,
        num_shards: int = 1,
        slack: float = 2.0,
        unique_rate: float = 0.5,
        **overrides,
    ) -> "AssemblyPlan":
        """Size every stage capacity from dataset shape (§II-B).

        Args:
          reads: anything with `num_reads` / `max_len` (ReadSet,
            ShardedReads) — only the shape is read.
          k_range: (k_min, k_max, k_step) iterative-k schedule.
          num_shards: planned execution width (1 = Local).
          slack: the single headroom dial every capacity scales with.
          unique_rate: expected unique-kmer : occurrence ratio (~1/coverage
            for clean data; →1 for error-heavy data).
          overrides: any AssemblyPlan field, overriding the derivation.
        """
        k_min, k_max, k_step = _normalize_k_range(k_range)
        R = int(reads.num_reads)
        L = int(reads.max_len)
        p2 = cap_lib.next_pow2
        windows = max(L - k_min + 1, 1)
        occ = R * windows                       # k-mer occurrences, k = k_min
        unique = max(int(unique_rate * occ), 1)
        # global owner/merged table: unique keys + slack
        kmer_capacity = max(1 << 10, p2(int(slack * unique)))
        # contigs: distinct assembled sequences are bounded by the unique
        # k-mer population over a minimum contig length (~2k at the floor)
        contig_cap = max(256, p2(int(slack * unique // (2 * k_min))))
        # assembled bases are bounded by unique k-mers; a single contig can
        # hold at most all of them (+ walked extensions)
        max_contig_len = int(min(max(1 << 11, p2(unique // 4)), 1 << 15))
        # (contig,mer) walk tables: distinct (contig, mer) pairs are
        # occurrence-collapsed, <= occ/2 in practice; slack buys probe room
        walk_capacity = max(1 << 12, p2(int(slack * occ / 2)))
        # link witnesses: <= 1 splint/read + 1 span/pair
        link_capacity = max(1 << 10, p2(int(slack * 3 * R // 2) // 4))
        max_scaffold_len = int(min(4 * max_contig_len, 1 << 16))
        base = dict(
            k_min=k_min, k_max=k_max, k_step=k_step,
            kmer_capacity=kmer_capacity,
            contig_cap=contig_cap,
            max_contig_len=max_contig_len,
            walk_capacity=walk_capacity,
            link_capacity=link_capacity,
            max_scaffold_len=max_scaffold_len,
            num_shards=num_shards,
            slack=slack,
            dataset_shape=(R, L),
        )
        base.update(overrides)
        return cls(**_clamp_contig_cap(base, overrides))

    @classmethod
    def from_stream(
        cls,
        batch_reads: int,
        max_len: int,
        k_range: tuple = (17, 21, 4),
        *,
        unique_kmers: Optional[int] = None,
        bloom_bits: Optional[int] = None,
        num_shards: int = 1,
        slack: float = 2.0,
        unique_rate: float = 0.1,
        total_reads: Optional[int] = None,
        **overrides,
    ) -> "AssemblyPlan":
        """Size a streaming plan from BATCH shape, not dataset size (§7).

        The defining property of the streamed path: `plan.bytes()` is a
        function of `batch_reads`, `max_len`, and the capacity estimates —
        `total_reads` is accepted for interface symmetry and deliberately
        ignored by every derivation, so the memory bill provably does not
        grow with dataset size (asserted in tests/test_stream.py).  What
        DOES bound the tables is the true (>= 2-sighting) k-mer
        population:

        Args:
          batch_reads: rows per streamed batch (even; whole pairs).
          max_len: batch column width (max read length).
          unique_kmers: estimate of the DISTINCT true k-mer population —
            community genome content, the paper's §II-B cardinality
            estimate.  Defaults to `unique_rate` x one batch's occurrence
            count, which assumes a single batch covers the community; pass
            it explicitly when it does not.
          bloom_bits: per-shard Bloom filter slots budget (the dial that
            trades filter memory against false-positive singleton
            admissions); default derives from the k-mer table size.
          total_reads: ignored for sizing (see above).
        """
        del total_reads  # sizing must not depend on dataset size
        k_min, k_max, k_step = _normalize_k_range(k_range)
        B = int(batch_reads)
        L = int(max_len)
        p2 = cap_lib.next_pow2
        windows = max(L - k_min + 1, 1)
        occ_batch = B * windows
        unique = max(int(unique_kmers or unique_rate * occ_batch), 1)
        kmer_capacity = max(1 << 10, p2(int(slack * unique)))
        contig_cap = max(256, p2(int(slack * unique // (2 * k_min))))
        max_contig_len = int(min(max(1 << 11, p2(unique // 4)), 1 << 15))
        # (contig, mer) pairs are occurrence-collapsed and bounded by
        # assembled bases x rungs — a function of `unique`, NOT of reads
        walk_capacity = max(1 << 12, p2(int(slack * 2 * unique)))
        # the link STORE is contig-pair scale (witnesses stream per batch)
        link_capacity = int(min(max(1 << 10, p2(int(slack * 16 * contig_cap))),
                                1 << 16))
        max_scaffold_len = int(min(4 * max_contig_len, 1 << 16))
        base = dict(
            k_min=k_min, k_max=k_max, k_step=k_step,
            kmer_capacity=kmer_capacity,
            contig_cap=contig_cap,
            max_contig_len=max_contig_len,
            walk_capacity=walk_capacity,
            link_capacity=link_capacity,
            max_scaffold_len=max_scaffold_len,
            num_shards=num_shards,
            slack=slack,
            batch_reads=B,
            bloom_bits=bloom_bits,
            dataset_shape=(B, L),
        )
        base.update(overrides)
        return cls(**_clamp_contig_cap(base, overrides))

    # ---- memory estimate ----

    def stage_bytes(self) -> dict:
        """Estimated peak static-buffer bytes per stage, per shard.

        Row-size constants mirror the dtypes of the actual buffers:
        occurrence lanes are 2 x uint32 + 2 x uint8 ext + bool; count
        tables are keys + count + two 4-wide int32 histograms (48 B); the
        seed index is a dual-lane DHT + 3 int32/bool side arrays.
        """
        R = self.dataset_shape[0] if self.dataset_shape else 0
        L = self.dataset_shape[1] if self.dataset_shape else 0
        per_shard_R = -(-R // self.num_shards) if R else 0
        windows = max(L - self.k_min + 1, 1) if L else 0
        occ_rows = per_shard_R * windows
        n_rungs = 3
        out = {
            # [R, W] hi/lo/left/right/valid occurrence lanes
            "kmer_occurrences": occ_rows * 11,
            # pre-combine + owner/merged count tables (48 B/row) +
            # finalized KmerSet (keys, count, hists, ext codes, used)
            "kmer_tables": (self.pre_cap if self.num_shards > 1 else
                            self.kmer_capacity) * 48
            + self.kmer_capacity * 48 * 2,
            "contigs": self.contig_cap * (self.max_contig_len + 12),
            "seed_index": self.seed_cap * 22,
            "alignments": per_shard_R * 2 * 20,
            "walk_tables": n_rungs * self.walk_capacity * 48,
            "links": self.link_capacity * 24,
            "scaffolds": self.contig_cap * (
                self.max_members * 9 + self.max_scaffold_len
            ),
        }
        if self.num_shards > 1:
            out["route_buffers"] = (
                self.num_shards * self.route_cap * 56
                + self.localize_out_factor * per_shard_R * (L + 8)
            )
        if self.batch_reads is not None:
            # two persistent Bloom filters (XLA bool = 1 byte/slot)
            out["bloom_filters"] = 2 * self.bloom_slots
        return out

    def bind(self, reads) -> "AssemblyPlan":
        """Copy of this plan with the dataset shape attached, so `bytes()`
        can price the read-proportional buffers."""
        return dataclasses.replace(
            self, dataset_shape=(int(reads.num_reads), int(reads.max_len))
        )

    def bytes(self) -> int:
        """Estimated peak working-set bytes per shard for one run."""
        return int(sum(self.stage_bytes().values()))


def plan_from(cfg, *, num_shards: int = 1) -> AssemblyPlan:
    """Map a legacy `PipelineConfig` onto an AssemblyPlan, field by field.

    `Assembler(plan_from(cfg), Local()).assemble(reads)` is numerically
    identical to the pre-facade `core.pipeline.assemble(reads, cfg)` —
    asserted in tests/test_api.py.
    """
    return AssemblyPlan(
        k_min=cfg.k_min, k_max=cfg.k_max, k_step=cfg.k_step,
        min_count=cfg.min_count, policy=cfg.policy,
        contig_pseudo_weight=cfg.contig_pseudo_weight,
        low_memory=cfg.low_memory,
        prune_alpha=cfg.prune_alpha, prune_beta=cfg.prune_beta,
        seed_stride=cfg.seed_stride,
        walk_ladder_step=cfg.walk_ladder_step,
        max_ext=cfg.max_ext, run_local_assembly=cfg.run_local_assembly,
        min_link_support=cfg.min_link_support, max_members=cfg.max_members,
        kmer_capacity=cfg.kmer_capacity, contig_cap=cfg.contig_cap,
        max_contig_len=cfg.max_contig_len,
        walk_capacity=cfg.walk_capacity, link_capacity=cfg.link_capacity,
        max_scaffold_len=cfg.max_scaffold_len,
        num_shards=num_shards,
    )
