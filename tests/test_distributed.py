"""Distributed runtime tests: run in a subprocess with 8 host devices.

The dry-run spec forbids setting XLA_FLAGS globally (smoke tests must see
one device), so multi-device tests spawn a fresh interpreter.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices_script(body: str, ndev: int = 8, timeout: int = 600) -> str:
    script = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
        import numpy as np
        import jax
        import jax.numpy as jnp
        assert jax.device_count() == {ndev}, jax.device_count()
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}/src:{REPO}/tests:{REPO}"
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_exchange_route_roundtrip_8dev():
    run_devices_script(
        """
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import exchange
        import functools

        S, n_per, cap = 8, 64, 32
        mesh = Mesh(np.array(jax.devices()), axis_names=("data",))
        rng = np.random.default_rng(0)
        vals = jnp.asarray(rng.integers(0, 1000, size=(S * n_per,)), jnp.int32)
        dest = jnp.asarray(rng.integers(0, S, size=(S * n_per,)), jnp.int32)

        def body(vals, dest):
            res = exchange.route(
                dest, (vals,), jnp.ones(vals.shape, bool),
                num_shards=S, capacity=cap, axis_name="data",
            )
            return res.payload[0], res.valid, res.overflow

        fn = jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data"), P()), check_vma=False)
        got, valid, ovf = fn(vals, dest)
        got, valid = np.asarray(got), np.asarray(valid)
        assert int(ovf) == 0, f"overflow {ovf}"
        # multiset of delivered values == multiset of sent values
        assert sorted(got[valid].tolist()) == sorted(np.asarray(vals).tolist())
        # owner correctness: shard s received exactly the dest==s items
        per_shard = S * cap
        for s in range(S):
            rows = slice(s * per_shard, (s + 1) * per_shard)
            mine = got[rows][valid[rows]]
            expect = np.asarray(vals)[np.asarray(dest) == s]
            assert sorted(mine.tolist()) == sorted(expect.tolist()), s
        print("EXCHANGE OK")
        """
    )


def test_distributed_kmer_analysis_matches_single_shard():
    run_devices_script(
        """
        from repro.core import kmer_analysis
        from repro.core.kmer_analysis import ExtensionPolicy
        from repro.data import mgsim
        from repro.dist import pipeline as dist

        genome, reads, _ = mgsim.single_genome_reads(51, genome_len=400,
                                                     coverage=20)
        mesh = dist.data_mesh(8)
        kset_sh, route_ovf, tab_ovf = dist.distributed_kmer_analysis(
            reads, mesh, k=21, pre_capacity=1 << 12, capacity=1 << 12,
        )
        assert int(route_ovf) == 0
        merged = dist.gather_ksets(kset_sh, capacity=1 << 13)
        # single-shard oracle
        ref = kmer_analysis.analyze(reads, k=21, capacity=1 << 13, min_count=2)
        ref_n = int(ref.used.sum())
        got_used = merged["count"] >= 2
        got_n = int(got_used.sum())
        assert got_n == ref_n, (got_n, ref_n)
        # counts per key identical: both sorted by key => direct compare
        import numpy as np
        ru = np.asarray(ref.used)
        np.testing.assert_array_equal(
            np.asarray(merged["hi"])[np.asarray(got_used)],
            np.asarray(ref.hi)[ru])
        np.testing.assert_array_equal(
            np.asarray(merged["count"])[np.asarray(got_used)],
            np.asarray(ref.count)[ru])
        print("DIST KMER OK", got_n)
        """
    )


def test_localize_reads_reports_overflow():
    """DESIGN.md §3.4: drive out_factor below the needed routing capacity;
    every dropped read must be COUNTED, never silently lost."""
    run_devices_script(
        """
        from repro.data import mgsim
        from repro.dist import pipeline as dist

        _, reads, _ = mgsim.single_genome_reads(55, genome_len=300,
                                                coverage=20)
        mesh = dist.data_mesh(8)
        reads8 = dist.shard_reads(reads, 8)
        R = reads8.num_reads
        n_valid = int(np.asarray(reads8.valid).sum())
        # worst-case skew: every read claims contig 0, owned by shard 0 —
        # shard 0's receive block (out_factor * R/8 rows) cannot hold them
        aln = jnp.zeros((R,), jnp.int32)
        localized, ovf = dist.localize_reads(reads8, aln, mesh,
                                             out_factor=1)
        delivered = int(np.asarray(localized.valid).sum())
        ovf = int(ovf)
        assert ovf > 0, "skewed routing must overflow the receiver budget"
        # conservation: delivered + reported drops == everything sent
        assert delivered + ovf == n_valid, (delivered, ovf, n_valid)
        # roomy budget: same exchange, nothing dropped
        localized2, ovf2 = dist.localize_reads(reads8, aln, mesh,
                                               out_factor=8)
        assert int(ovf2) == 0, int(ovf2)
        assert int(np.asarray(localized2.valid).sum()) == n_valid
        print("LOCALIZE OVERFLOW OK", ovf)
        """
    )


def test_mesh_assemble_matches_local():
    """Acceptance: Assembler(plan, Mesh(8)).assemble runs the FULL pipeline
    (contig rounds + scaffolding) on an 8-device mesh, and its scaffold
    stats match the Local() run within bench_quality's tolerance."""
    run_devices_script(
        """
        import warnings
        warnings.simplefilter("ignore", DeprecationWarning)
        from repro.api import Assembler, AssemblyPlan, Local, Mesh
        from repro.data import mgsim
        from benchmarks import metrics

        comm = mgsim.sample_community(5, num_genomes=3, genome_len=300,
                                      abundance_sigma=0.3)
        reads, _ = mgsim.generate_reads(6, comm, num_pairs=400, read_len=60,
                                        err_rate=0.003)
        # localize_out_factor=8: a 3-genome community assembles into a
        # handful of contigs, so contig ownership (c mod S) is maximally
        # skewed — give every shard room for the whole read set so the
        # zero-overflow assertion below is meaningful
        plan = AssemblyPlan.from_dataset(reads, (17, 21, 4), num_shards=8,
                                         unique_rate=0.2,
                                         localize_out_factor=8)
        out_l = Assembler(plan, Local()).assemble(reads)
        out_m = Assembler(plan, Mesh(num_shards=8)).assemble(reads)

        def quality(out):
            lens = np.asarray(out["scaffold_seqs"].lengths)
            bases = np.asarray(out["scaffold_seqs"].bases)
            pieces = [bases[i, : lens[i]] for i in range(len(lens))
                      if lens[i] >= 60]
            return metrics.evaluate(pieces, comm.genomes)

        ql, qm = quality(out_l), quality(out_m)
        print(f"local gf={ql['genome_fraction']:.3f} n50={ql['n50']}")
        print(f"mesh  gf={qm['genome_fraction']:.3f} n50={qm['n50']}")
        print(f"mesh overflow: {out_m['overflow']}")
        # bench_quality tolerance: genome fraction within 0.02
        assert qm["genome_fraction"] >= ql["genome_fraction"] - 0.02, (ql, qm)
        assert qm["misassemblies"] <= ql["misassemblies"] + 1, (ql, qm)
        # nothing silently dropped on the mesh path
        assert all(v == 0 for v in out_m["overflow"].values()), (
            out_m["overflow"])
        print("MESH E2E OK")
        """,
        # Local + Mesh end-to-end in one interpreter: dominated by XLA
        # compiles of the per-round shard_map programs on host devices
        timeout=2400,
    )


def test_mesh_backend_parity():
    """Kernel backend parity under the Mesh(8) owner exchange (DESIGN.md §8).

    Two layers, both bit-exact:
      * sharded k-mer analysis — canonical keys, counts, extension
        histograms, and per-shard owner placement identical whether the
        shard bodies extract through the Pallas kernel or the jnp ref
        (owner placement compares the FULL flat [S * cap] layout, so a
        key landing on a different shard would fail even with equal
        global multisets);
      * the full Mesh(8) `assemble` — identical scaffolds.
    Combined with the Local twins in tests/test_kernel_parity.py, every
    (context, backend) pair produces one answer."""
    run_devices_script(
        """
        import dataclasses
        from repro.api import Assembler, AssemblyPlan, Mesh
        from repro.data import mgsim
        from repro.dist import pipeline as dist, stages

        comm = mgsim.sample_community(5, num_genomes=3, genome_len=300,
                                      abundance_sigma=0.3)
        reads, _ = mgsim.generate_reads(6, comm, num_pairs=400, read_len=60,
                                        err_rate=0.003)
        mesh = dist.data_mesh(8)
        ksets = {}
        for backend in ("pallas", "ref"):
            kset, route_ovf, tab_ovf = stages.sharded_kmer_analysis(
                dist.shard_reads(reads, 8), mesh, k=21,
                pre_capacity=1 << 14, capacity=1 << 14, backend=backend)
            assert int(route_ovf) == 0 and int(tab_ovf) == 0
            ksets[backend] = kset
        for a, b in zip(jax.tree.leaves(ksets["pallas"]),
                        jax.tree.leaves(ksets["ref"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("MESH KSET PARITY OK")

        plan = AssemblyPlan.from_dataset(reads, (21, 21, 4), num_shards=8,
                                         unique_rate=0.2,
                                         localize_out_factor=8)
        outs = {}
        for backend in ("pallas", "ref"):
            p = dataclasses.replace(plan, kernel_backend=backend)
            outs[backend] = Assembler(p, Mesh(num_shards=8)).assemble(reads)
        for key in ("scaffold_seqs", "contigs", "alive"):
            for a, b in zip(jax.tree.leaves(outs["pallas"][key]),
                            jax.tree.leaves(outs["ref"][key])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        lens = np.asarray(outs["pallas"]["scaffold_seqs"].lengths)
        assert int(lens.sum()) > 0
        print("MESH BACKEND PARITY OK")
        """,
        # two full mesh assembles in one interpreter; compile-bound
        timeout=2400,
    )


def test_mesh_walk_backend_parity():
    """Walk kernel parity under Mesh(8) ownership (DESIGN.md §8).

    Two layers, both bit-exact across `ops.mer_walk` backends:
      * `stages.sharded_extend` — per-shard localized walk tables, walks
        over owned contig ends only, ownership combine: the extended
        ContigSet must be identical whether each shard body walks through
        the fused Pallas kernel or the jnp ref;
      * the full Mesh(8) `assemble` — identical scaffolds (this also runs
        the gap-closing target-stop walks).
    Combined with the Local twins in tests/test_walk_parity.py, every
    (context, backend) walk pair produces one answer."""
    run_devices_script(
        """
        import dataclasses
        from repro.api import Assembler, AssemblyPlan, Mesh
        from repro.core import alignment, pipeline as pipe
        from repro.data import mgsim
        from repro.dist import pipeline as dist, stages

        comm = mgsim.sample_community(75, num_genomes=3, genome_len=300,
                                      abundance_sigma=0.3)
        reads, _ = mgsim.generate_reads(76, comm, num_pairs=400, read_len=60,
                                        err_rate=0.003)
        # contigs from a fixed-backend Local round; only the walk under
        # test varies below
        cfg = pipe.PipelineConfig(k_min=21, k_max=21,
                                  kmer_capacity=1 << 14, contig_cap=256,
                                  max_contig_len=2048,
                                  run_local_assembly=False)
        import warnings
        warnings.simplefilter("ignore", DeprecationWarning)
        contigs, alive, al, _ = pipe.iterative_contig_generation(reads, cfg)
        mesh = dist.data_mesh(8)
        reads8 = dist.shard_reads(reads, 8)  # 800 reads: no padding
        exts = {}
        for backend in ("pallas", "ref"):
            ext, ovf = stages.sharded_extend(
                reads8, contigs, alive, al, mesh,
                mer_sizes=(17, 21, 25), capacity=1 << 14, max_ext=48,
                out_factor=8, backend=backend)
            exts[backend] = ext
        for a, b in zip(jax.tree.leaves(exts["pallas"]),
                        jax.tree.leaves(exts["ref"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        grew = int((np.asarray(exts["ref"].lengths)
                    > np.asarray(contigs.lengths)).sum())
        assert grew > 0, "per-shard walk must extend something"
        print("SHARDED EXTEND PARITY OK", grew)

        plan = AssemblyPlan.from_dataset(reads, (17, 21, 4), num_shards=8,
                                         unique_rate=0.2,
                                         localize_out_factor=8)
        outs = {}
        for backend in ("pallas", "ref"):
            p = dataclasses.replace(plan, kernel_backend=backend)
            outs[backend] = Assembler(p, Mesh(num_shards=8)).assemble(reads)
        for key in ("scaffold_seqs", "contigs", "alive"):
            for a, b in zip(jax.tree.leaves(outs["pallas"][key]),
                            jax.tree.leaves(outs["ref"][key])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        lens = np.asarray(outs["pallas"]["scaffold_seqs"].lengths)
        assert int(lens.sum()) > 0
        print("MESH WALK BACKEND PARITY OK")
        """,
        # sharded extend x2 + two full mesh assembles; compile-bound
        timeout=2400,
    )


def test_stream_assemble_mesh_matches_in_memory():
    """CI parity smoke (ISSUE 3): Assembler.assemble_stream over a small
    mgsim dataset split into >= 2 batches, on an 8-device mesh with the
    owner-partitioned two-pass Bloom ingest, must reproduce the in-memory
    Local scaffolds (bench_quality tolerance; in practice bit-identical —
    asserted, since every fold in the streamed path is exact)."""
    run_devices_script(
        """
        from repro.api import Assembler, AssemblyPlan, Local, Mesh
        from repro.data import mgsim
        from repro.stream import batches_from_readset

        comm = mgsim.sample_community(5, num_genomes=3, genome_len=300,
                                      abundance_sigma=0.3)
        reads, _ = mgsim.generate_reads(6, comm, num_pairs=400, read_len=60,
                                        err_rate=0.003)
        plan = AssemblyPlan.from_dataset(reads, (17, 21, 4), num_shards=8,
                                         unique_rate=0.2)
        out_mem = Assembler(plan, Local()).assemble(reads)
        batches = batches_from_readset(reads, 256)
        assert len(batches) >= 2, len(batches)
        out_st = Assembler(plan, Mesh(num_shards=8)).assemble_stream(batches)
        for a, b in zip(jax.tree.leaves(out_mem["scaffold_seqs"]),
                        jax.tree.leaves(out_st["scaffold_seqs"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert all(v == 0 for v in out_st["overflow"].values()), (
            out_st["overflow"])
        print("STREAM MESH PARITY OK")
        """,
        # in-memory Local + streamed Mesh in one interpreter; compile-bound
        timeout=2400,
    )


def test_serving_concurrent_jobs_on_shared_mesh():
    """Serving smoke (ISSUE 7): two streaming jobs multiplexed onto ONE
    shared Mesh(8) — each on its own ctx.spawn() of the same jax mesh —
    must be bit-identical to solo `assemble_stream` runs; then a job
    killed mid-stream resumes on a restarted server (same journal +
    checkpoint roots) and finishes bit-identically too."""
    run_devices_script(
        """
        import dataclasses, os, tempfile
        from repro.api import Assembler, AssemblyPlan, Mesh
        from repro.data import mgsim
        from repro.serving import JobServer, JobSpec, JobState
        from repro.stream import batches_from_readset

        comm = mgsim.sample_community(5, num_genomes=2, genome_len=300,
                                      abundance_sigma=0.3)
        srcs, solos = [], []
        plan = AssemblyPlan.from_stream(64, 50, (17, 17, 4), num_shards=8)
        mesh = Mesh(num_shards=8)
        for seed in (6, 9):
            reads, _ = mgsim.generate_reads(seed, comm, num_pairs=96,
                                            read_len=50, err_rate=0.003)
            srcs.append(batches_from_readset(reads, 64))
            solos.append(Assembler(plan, mesh.spawn()).assemble_stream(
                srcs[-1]))

        def assert_same(want, got):
            a, b = dict(want), dict(got)
            sa, sb = a.pop("stream_stats"), b.pop("stream_stats")
            assert ({k: dataclasses.replace(v, resumed=False)
                     for k, v in sa.items()}
                    == {k: dataclasses.replace(v, resumed=False)
                        for k, v in sb.items()})
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

        root = tempfile.mkdtemp()
        jdir, cdir = os.path.join(root, "j"), os.path.join(root, "c")
        srv = JobServer(mesh, budget_bytes=4 * plan.bytes(),
                        journal_dir=jdir, checkpoint_root=cdir)
        a = srv.submit(JobSpec("a", batches=srcs[0], plan=plan))
        b = srv.submit(JobSpec("b", batches=srcs[1], plan=plan))
        ticks = 0
        while srv.step():
            ticks += 1
            if ticks == 3 and b.state == JobState.RUNNING:
                break  # "crash" with b mid-stream
        assert a.events > 0 and b.events > 0  # both really interleaved

        srv2 = JobServer(mesh, budget_bytes=4 * plan.bytes(),
                         journal_dir=jdir, checkpoint_root=cdir)
        srv2.recover([JobSpec("a", batches=srcs[0], plan=plan),
                      JobSpec("b", batches=srcs[1], plan=plan)])
        srv2.run()
        for job, solo in ((srv2.jobs["a"], solos[0]),
                          (srv2.jobs["b"], solos[1])):
            assert job.state == JobState.DONE, (job.name, job.error)
            assert_same(solo, srv2.result(job.name))
        print("SERVING MESH OK", ticks)
        """,
        # two solo + two multiplexed streamed mesh runs; compile-bound
        timeout=2400,
    )


def test_read_localization_improves_owner_locality():
    run_devices_script(
        """
        import functools
        from repro.core import alignment, pipeline as pipe
        from repro.core.kmer_analysis import ExtensionPolicy
        from repro.data import mgsim
        from repro.dist import pipeline as dist

        comm = mgsim.sample_community(52, num_genomes=4, genome_len=400,
                                      abundance_sigma=0.2)
        reads, _ = mgsim.generate_reads(53, comm, num_pairs=400, read_len=60)
        mesh = dist.data_mesh(8)
        cfg = pipe.PipelineConfig(k_min=21, k_max=21,
                                  kmer_capacity=1 << 14, contig_cap=256,
                                  max_contig_len=2048, run_local_assembly=False)
        contigs, alive, al, _ = pipe.iterative_contig_generation(reads, cfg)
        reads8 = dist.shard_reads(reads, 8)
        aln_c = al.contig[:, 0]

        def locality(readset, aln_contig):
            # seed index owner = contig % 8; read is local if it sits on the
            # shard owning its aligned contig
            R = readset.num_reads
            per = R // 8
            shard_of_read = np.arange(R) // per
            owner = np.where(np.asarray(aln_contig) >= 0,
                             np.asarray(aln_contig) % 8, shard_of_read[:R])
            ok = np.asarray(aln_contig) >= 0
            return float((owner[ok] == shard_of_read[:R][ok]).mean())

        before = locality(reads8, np.asarray(aln_c)[:reads8.num_reads])
        localized, ovf = dist.localize_reads(reads8, aln_c, mesh)
        # realign localized reads to find their contigs again
        sidx = alignment.build_seed_index(contigs, alive, seed_len=21,
                                          capacity=1 << 14)
        al2 = alignment.align_reads(localized, contigs, sidx, seed_len=21)
        after = locality(localized, np.asarray(al2.contig[:, 0]))
        print(f"LOCALITY before={before:.3f} after={after:.3f}")
        assert after > 0.9, after
        assert after > before + 0.3, (before, after)
        """
    )
