"""Kernel and walk backend parity under Mesh(8) ownership (DESIGN.md §8).

Each test runs its script in a fresh interpreter (`helpers.
run_devices_script`): the 8 host devices need XLA_FLAGS set before jax is
imported, and the test workers' own jax must keep seeing one device.  Every
such script shares one time limit, `helpers.DEVICES_SCRIPT_TIMEOUT_S`
(360 s, about three times the slowest); the Mesh stages compile once per
shape (`repro.dist.stages`), so a script that runs past it is a fault to
see, not a limit to raise.  The tests are split over several files so
that `--dist loadfile` can give them to different workers.
"""
from helpers import run_devices_script


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
        """
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
        """
    )
