"""Mesh(8) against Local: whole assemblies in memory and streamed.

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
        """
    )


def test_stream_assemble_mesh_matches_in_memory():
    """CI parity smoke: Assembler.assemble_stream over a small
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
        """
    )
