"""repro.serving: job server, plan-priced admission, pause/resume/recover.

The acceptance bar for the serving subsystem (ISSUE 7):
  * two streaming jobs multiplexed on ONE shared context produce
    bit-identical results to solo `assemble_stream` runs (the Mesh(8)
    twin lives in tests/test_mesh_serving.py);
  * a job killed mid-stream resumes after a server restart and finishes
    bit-identically (journal + per-job StreamCheckpoint);
  * admission control provably refuses an over-budget job and backfills
    a smaller later job past a blocked head-of-queue.
"""
import dataclasses
import json
import os
import tempfile
import types

import numpy as np
import jax
import pytest

from repro.api import Assembler, AssemblyPlan, Local
from repro.api.assembler import STAGES, drive
from repro.data import mgsim
from repro.serving import (
    BudgetScheduler,
    JobError,
    JobServer,
    JobSpec,
    JobState,
    Unschedulable,
    to_cwl,
    workflow,
)
from repro.serving.jobs import Job, price
from repro.stream import batches_from_readset, job_checkpoint_dir


# ---------------------------------------------------------------------------
# specs, pricing, workflow declaration (no pipeline compute)
# ---------------------------------------------------------------------------


def test_jobspec_requires_exactly_one_source():
    with pytest.raises(JobError, match="exactly one"):
        JobSpec("both", reads=object(), batches=object()).validate()
    with pytest.raises(JobError, match="exactly one"):
        JobSpec("neither").validate()
    with pytest.raises(JobError, match="name"):
        JobSpec("", reads=object()).validate()


def test_price_binds_dataset_for_admission():
    _, reads, _ = mgsim.single_genome_reads(7, genome_len=200, coverage=5)
    plan = price(JobSpec("j", reads=reads))
    assert plan.dataset_shape == (int(reads.num_reads), int(reads.max_len))
    assert plan.bytes() > 0
    # an explicit unbound plan gets bound too, so bytes() prices the
    # read-proportional buffers instead of treating them as zero
    explicit = price(JobSpec("j", reads=reads, plan=AssemblyPlan()))
    assert explicit.dataset_shape is not None


def test_workflow_steps_cover_every_stage_byte():
    plan = AssemblyPlan.from_stream(256, 60, (17, 21, 4), num_shards=4)
    steps = workflow(plan)
    assert tuple(s.name for s in steps) == STAGES
    assert sum(s.bytes for s in steps) == plan.bytes()
    by_name = {s.name: s for s in steps}
    assert "bloom_filters" in by_name["analyze"].buffers  # stream plan
    assert "route_buffers" in by_name["align"].buffers    # sharded plan


def test_to_cwl_shape():
    plan = AssemblyPlan.from_stream(256, 60, (17, 21, 4))
    doc = to_cwl(plan, name="wetlands")
    assert doc["class"] == "Workflow"
    assert tuple(doc["steps"]) == STAGES
    # steps chain linearly: reads -> analyze -> ... -> scaffold
    assert doc["steps"]["analyze"]["in"]["data"] == "reads"
    assert doc["steps"]["scaffold"]["in"]["data"] == "align/out"
    assert doc["outputs"]["scaffolds"]["outputSource"] == "scaffold/out"
    for name, step in doc["steps"].items():
        (req,) = step["requirements"]
        assert req["class"] == "ResourceRequirement"
        assert req["ramMin"] >= 1


# ---------------------------------------------------------------------------
# state machine
# ---------------------------------------------------------------------------


def _job(name="j", cost=100, priority=0, seq=0):
    """A Job stand-in with just the fields the scheduler/state code uses."""
    job = types.SimpleNamespace(name=name, cost=cost, priority=priority,
                                seq=seq)
    return job


def test_state_machine_legal_path():
    plan = AssemblyPlan.from_stream(64, 50, (17, 17, 4))
    job = Job(JobSpec("j", batches=object(), plan=plan), plan, seq=1)
    assert job.state == JobState.QUEUED
    for st in (JobState.ADMITTED, JobState.RUNNING, JobState.PAUSED,
               JobState.QUEUED, JobState.ADMITTED, JobState.RUNNING,
               JobState.DONE):
        job.transition(st)
    assert job.finished_at is not None


def test_state_machine_rejects_illegal_transitions():
    plan = AssemblyPlan.from_stream(64, 50, (17, 17, 4))
    job = Job(JobSpec("j", batches=object(), plan=plan), plan, seq=1)
    with pytest.raises(JobError, match="QUEUED -> RUNNING"):
        job.transition(JobState.RUNNING)  # cannot skip admission
    job.transition(JobState.CANCELLED)
    with pytest.raises(JobError, match="CANCELLED"):
        job.transition(JobState.QUEUED)   # terminal states are final


# ---------------------------------------------------------------------------
# scheduler: budget, priority, backfill
# ---------------------------------------------------------------------------


def test_scheduler_priority_then_fifo():
    s = BudgetScheduler(1000)
    lo_old = _job("lo-old", cost=10, priority=0, seq=1)
    hi_new = _job("hi-new", cost=10, priority=5, seq=3)
    lo_new = _job("lo-new", cost=10, priority=0, seq=2)
    assert s.pick([lo_old, hi_new, lo_new]) is hi_new
    assert s.pick([lo_old, lo_new]) is lo_old


def test_scheduler_backfill_past_blocked_head():
    s = BudgetScheduler(1000)
    running = _job("running", cost=800)
    s.reserve(running)
    big = _job("big", cost=500, priority=9, seq=1)   # head of queue, blocked
    small = _job("small", cost=150, priority=0, seq=2)
    assert not s.fits(big)
    assert s.pick([big, small]) is small             # backfill
    s.reserve(small)
    assert s.pick([big]) is None                     # still blocked
    s.release(running)
    assert s.pick([big]) is big                      # head runs when space frees
    # release is idempotent and returns the budget
    s.release(small)
    s.release(small)
    assert s.free == 1000


def test_scheduler_refuses_unschedulable():
    s = BudgetScheduler(100)
    with pytest.raises(Unschedulable, match="needs 500"):
        s.check(_job("huge", cost=500))
    s.check(_job("ok", cost=100))  # exactly at budget is schedulable


def test_scheduler_double_reserve_rejected():
    s = BudgetScheduler(100)
    job = _job("j", cost=40)
    s.reserve(job)
    with pytest.raises(RuntimeError, match="already holds"):
        s.reserve(job)


# ---------------------------------------------------------------------------
# server admission + lifecycle (fake generators: no pipeline compute)
# ---------------------------------------------------------------------------


def _fake_start(server, events=2):
    """Patch JobServer._start to run a stub staged generator, so
    admission/lifecycle tests never touch the assembly pipeline."""

    def start(job):
        def gen():
            for i in range(events):
                yield STAGES[min(i, len(STAGES) - 1)], {"i": i}
            return {"job": job.name}

        job._gen = gen()
        job.transition(JobState.RUNNING)
        server._journal(job, "started", resumed=job.resumed)

    server._start = start


def _stream_plan(**kw):
    return AssemblyPlan.from_stream(64, 50, (17, 17, 4), **kw)


def test_server_refuses_over_budget_job():
    plan = _stream_plan()
    srv = JobServer(Local(), budget_bytes=plan.bytes() // 2)
    job = srv.submit(JobSpec("too-big", batches=object(), plan=plan))
    assert job.state == JobState.FAILED
    assert "budget" in job.error
    assert srv.scheduler.reserved == 0  # refused jobs hold nothing


def test_server_backfill_admits_smaller_later_job():
    plan = _stream_plan()
    one = plan.bytes()
    # budget fits one job; the high-priority head is twice that
    big = dataclasses.replace(plan, kmer_capacity=plan.kmer_capacity * 8)
    assert big.bytes() > one
    srv = JobServer(Local(), budget_bytes=big.bytes() + one)
    _fake_start(srv, events=3)
    a = srv.submit(JobSpec("big", batches=object(), plan=big, priority=9))
    b = srv.submit(JobSpec("small", batches=object(), plan=plan))
    srv.step()
    # big admitted first (priority), small backfilled into the residue
    assert a.state == JobState.RUNNING
    assert b.state == JobState.RUNNING
    c = srv.submit(JobSpec("waits", batches=object(), plan=big))
    srv.step()
    assert c.state == JobState.QUEUED  # no room until a job finishes
    srv.run()
    assert {j.state for j in (a, b, c)} == {JobState.DONE}
    assert srv.result("big") == {"job": "big"}
    assert srv.scheduler.reserved == 0


def test_server_cancel_queued_and_running():
    plan = _stream_plan()
    srv = JobServer(Local(), budget_bytes=plan.bytes() * 4)
    _fake_start(srv, events=50)
    a = srv.submit(JobSpec("a", batches=object(), plan=plan))
    b = srv.submit(JobSpec("b", batches=object(), plan=plan))
    srv.cancel("b")                       # still QUEUED: immediate
    assert b.state == JobState.CANCELLED
    srv.step()
    assert a.state == JobState.RUNNING
    srv.cancel("a")                       # RUNNING: at the next boundary
    assert a.state == JobState.RUNNING
    srv.step()
    assert a.state == JobState.CANCELLED
    assert a.events == 1                  # stopped mid-workflow
    assert srv.scheduler.reserved == 0
    with pytest.raises(JobError, match="not DONE|CANCELLED"):
        srv.result("a")


def test_server_duplicate_active_name_rejected():
    plan = _stream_plan()
    srv = JobServer(Local(), budget_bytes=plan.bytes() * 4)
    srv.submit(JobSpec("j", batches=object(), plan=plan))
    with pytest.raises(JobError, match="already active"):
        srv.submit(JobSpec("j", batches=object(), plan=plan))


def test_server_journal_records_lifecycle(tmp_path):
    plan = _stream_plan()
    srv = JobServer(Local(), budget_bytes=plan.bytes() * 2,
                    journal_dir=str(tmp_path))
    _fake_start(srv, events=2)
    srv.submit(JobSpec("j", batches=object(), plan=plan))
    srv.run()
    with open(tmp_path / "journal.jsonl") as f:
        recs = [json.loads(line) for line in f]
    assert [r["event"] for r in recs] == [
        "submitted", "admitted", "started", "stage", "stage", "done"
    ]
    assert srv.journal_replay() == {"j": "DONE"}


def test_job_checkpoint_dir_is_safe_and_distinct():
    a = job_checkpoint_dir("/r", "job A/1")
    b = job_checkpoint_dir("/r", "job A 1")
    assert a != b                          # slug collision disambiguated
    assert a == job_checkpoint_dir("/r", "job A/1")  # deterministic
    assert "/" not in os.path.basename(a)
    assert os.path.dirname(a) == "/r"


# ---------------------------------------------------------------------------
# the real thing: streaming jobs on one shared context
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stream_world():
    comm = mgsim.sample_community(seed=1, num_genomes=2, genome_len=300,
                                  abundance_sigma=0.5)
    reads, _ = mgsim.generate_reads(seed=2, community=comm, num_pairs=96,
                                    read_len=50, err_rate=0.004)
    src = batches_from_readset(reads, 64)
    plan = AssemblyPlan.from_stream(64, int(reads.max_len), (17, 21, 4))
    solo = Assembler(plan, Local()).assemble_stream(src)
    return comm, src, plan, solo


def assert_same_assembly(a, b):
    """Bit-identical up to StreamStats.resumed (checkpoint bookkeeping)."""
    a, b = dict(a), dict(b)
    norm = lambda ss: {k: dataclasses.replace(v, resumed=False)
                       for k, v in ss.items()}
    assert norm(a.pop("stream_stats")) == norm(b.pop("stream_stats"))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_concurrent_stream_jobs_bit_identical_to_solo(stream_world, tmp_path):
    comm, src, plan, solo = stream_world
    reads2, _ = mgsim.generate_reads(seed=9, community=comm, num_pairs=96,
                                     read_len=50, err_rate=0.004)
    src2 = batches_from_readset(reads2, 64)
    solo2 = Assembler(plan, Local()).assemble_stream(src2)

    # checkpoint_root on: each job binds its OWN checkpoint dir, so this
    # also pins ctx.spawn() — on a shared context instance, job b's
    # prepare_stream would clobber job a's binding and fingerprint-fail
    srv = JobServer(Local(), budget_bytes=4 * plan.bytes(),
                    checkpoint_root=str(tmp_path))
    a = srv.submit(JobSpec("a", batches=src, plan=plan))
    b = srv.submit(JobSpec("b", batches=src2, plan=plan))
    srv.run()
    # both ran interleaved on ONE shared context...
    assert a.state == b.state == JobState.DONE
    assert min(a.events, b.events) > 0
    # ...and neither perturbed the other
    assert_same_assembly(solo, srv.result("a"))
    assert_same_assembly(solo2, srv.result("b"))


def test_pause_resume_bit_identical(stream_world, tmp_path):
    _, src, plan, solo = stream_world
    srv = JobServer(Local(), budget_bytes=4 * plan.bytes(),
                    checkpoint_root=str(tmp_path))
    job = srv.submit(JobSpec("j", batches=src, plan=plan))
    ticks = 0
    while srv.step():
        ticks += 1
        if ticks == 2:
            srv.pause("j")
        if job.state == JobState.PAUSED:
            assert srv.scheduler.reserved == 0  # pause releases the budget
            srv.resume("j")
    assert job.state == JobState.DONE
    assert job.resumed
    assert_same_assembly(solo, srv.result("j"))


def test_kill_and_restart_resumes_bit_identical(stream_world, tmp_path):
    _, src, plan, solo = stream_world
    jdir, cdir = str(tmp_path / "journal"), str(tmp_path / "ckpt")
    spec = lambda: JobSpec("crashy", batches=src, plan=plan)

    srv = JobServer(Local(), budget_bytes=4 * plan.bytes(),
                    journal_dir=jdir, checkpoint_root=cdir)
    job = srv.submit(spec())
    for _ in range(4):  # die mid-stream
        srv.step()
    assert job.state == JobState.RUNNING
    del srv

    srv2 = JobServer(Local(), budget_bytes=4 * plan.bytes(),
                     journal_dir=jdir, checkpoint_root=cdir)
    srv2.recover([spec()])
    job2 = srv2.jobs["crashy"]
    assert job2.state == JobState.QUEUED and job2.resumed
    srv2.run()
    assert job2.state == JobState.DONE
    out = srv2.result("crashy")
    # the k-mer analysis fast-forwarded from the per-job checkpoint
    assert any(s.resumed for s in out["stream_stats"].values())
    assert_same_assembly(solo, out)

    # a third recover sees DONE in the journal and does not re-run
    srv3 = JobServer(Local(), budget_bytes=4 * plan.bytes(),
                     journal_dir=jdir, checkpoint_root=cdir)
    srv3.recover([spec()])
    assert srv3.jobs["crashy"].state == JobState.DONE
    assert not srv3.step()  # nothing left to do


def test_hook_abort_stops_assemble(stream_world):
    """drive()'s hook is the cancellation seam: raising aborts cleanly."""
    _, src, plan, _ = stream_world

    class Stop(Exception):
        pass

    seen = []

    def hook(stage, info):
        seen.append(stage)
        raise Stop()

    with pytest.raises(Stop):
        Assembler(plan, Local()).assemble_stream(src, hook=hook)
    assert seen == ["analyze"]


def test_drive_returns_generator_value():
    def gen():
        yield "analyze", {}
        return {"x": 1}

    assert drive(gen()) == {"x": 1}
    events = []
    assert drive(gen(), lambda s, i: events.append(s)) == {"x": 1}
    assert events == ["analyze"]
