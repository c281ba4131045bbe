"""Mesh(8) exchange checks: the owner route, sharded k-mer analysis against
the single-shard oracle, and read localization (overflow accounting and
owner locality).

Each test runs its script in a fresh interpreter (`helpers.
run_devices_script`): the 8 host devices need XLA_FLAGS set before jax is
imported, and the test workers' own jax must keep seeing one device.  Every
such script shares one time limit, `helpers.DEVICES_SCRIPT_TIMEOUT_S`
(360 s, about three times the slowest); the Mesh stages compile once per
shape (`repro.dist.stages`), so a script that runs past it is a fault to
see, not a limit to raise.  The tests are split over several files so
that `--dist loadfile` can give them to different workers.
"""
import pytest

import helpers
from helpers import run_devices_script


def test_devices_script_timeout_fails_with_output(monkeypatch):
    monkeypatch.setattr(helpers, "DEVICES_SCRIPT_TIMEOUT_S", 30)
    with pytest.raises(pytest.fail.Exception,
                       match="(?s)ran past 30 s.*STDOUT:.*REACHED"):
        run_devices_script(
            """
            print("REACHED", flush=True)
            import time
            time.sleep(600)
            """
        )


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
