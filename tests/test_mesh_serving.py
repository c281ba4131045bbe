"""Serving on a shared Mesh(8): concurrent jobs and kill-and-restart resume.

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


def test_serving_concurrent_jobs_on_shared_mesh():
    """Serving smoke: two streaming jobs multiplexed onto ONE
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
        """
    )
