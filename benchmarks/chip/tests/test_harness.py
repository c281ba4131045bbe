"""The trace reduction, the work function, and refusing a run off the chip."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from conftest import BENCH, ROOT

import run
from lib import tracing


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    """A trace with the plane and line names of a TPU trace: two timed
    stages of one assembly, then the check's copies; modules of three
    layers and one that no layer file lists, an idle gap in each stage."""
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit__occurrences(11)", 50, 100),      # starts before window
            ev("jit_traverse(12)", 200, 300),
            ev("jit__accumulate_walk_tables(13)", 600, 200),
            ev("jit_broadcast_in_dim(14)", 850, 50),
            ev("jit__close_and_render(15)", 1000, 300),  # ends after
            ev("jit_convert_element_type(16)", 1250, 20),  # in the check
        ]),
        NS(name="XLA Ops", events=[ev("fusion.1", 200, 10)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("a1/0", 100, 560),
        ev("a1/1", 660, 540),
        ev("a1/check", 1200, 100),
        ev("unrelated", 0, 5000),
    ])])
    return [dev, host, NS(name="/host:metadata", lines=[])]


SPANS = {"a1/0": "contig_rounds k=17", "a1/1": "close_gaps"}


def layer_specs():
    return [{"name": n, "spec": json.load(open(os.path.join(
        BENCH, "layer_metrics", n + ".json")))}
        for n in sorted(f[:-5] for f in os.listdir(
            os.path.join(BENCH, "layer_metrics")))]


def test_timed_stretches_clip_modules_and_count_busy_time():
    tw = tracing.from_planes(planes(), SPANS)
    assert tw.window_s == pytest.approx(1100e-9)
    # occurrences clipped to [100, 150), render to [1000, 1200)
    assert tw.busy_s == pytest.approx((50 + 300 + 200 + 50 + 200) * 1e-9)


def test_layers_and_unmatched_account_for_busy_time():
    tw = tracing.from_planes(planes(), SPANS)
    specs = [m for m in layer_specs() if m["spec"]["reader"] == "device_time"]
    layers = sum(tw.module_seconds(m["spec"]["modules"]) for m in specs)
    assert tw.unmatched_s(specs) == pytest.approx(50e-9)
    assert layers + tw.unmatched_s(specs) == pytest.approx(tw.busy_s)


def test_every_jitted_stage_has_one_layer():
    """No module is claimed by two device-time layer files."""
    specs = [m for m in layer_specs() if m["spec"]["reader"] == "device_time"]
    seen = {}
    for m in specs:
        for p in m["spec"]["modules"]:
            assert p not in seen, (p, seen.get(p), m["name"])
            seen[p] = m["name"]


def test_readers():
    tw = tracing.from_planes(planes(), SPANS)
    ctx = {"assemblies": 2, "peaks": {"hbm_bytes_per_s": 1e9},
           "shapes": {"reads": [10, 150], "walk_capacity": 64,
                      "walk_table_builds": [[13, 17, 21]]},
           "work": lambda name: run.load_module("work", name)}
    by = {m["name"]: m["spec"] for m in layer_specs()}
    read = lambda n: run.load_module("readers", by[n]["reader"]).read(
        tw, by[n], ctx)
    assert read("contig_graph.device_s") == pytest.approx(150e-9)
    assert read("alignment.device_s") is None  # nothing to read: no metric
    idle = read("device.idle_share")
    assert idle == pytest.approx(1 - 800 / 1100)
    work = run.load_module("work", "walk_tables").hbm_bytes(ctx["shapes"])
    assert read("walk_tables_roofline") == pytest.approx(
        100 * 2 * work / (1e9 * 200e-9))


def test_idle_gaps_named_by_stage():
    tw = tracing.from_planes(planes(), SPANS)
    gaps = dict(tw.breakdown()["idle_gaps"])
    # [150, 200) and [500, 600) inside the first stage, [800, 850) and
    # [900, 1000) inside the second
    assert gaps == pytest.approx({"contig_rounds k=17": 150e-9,
                                  "close_gaps": 150e-9})
    ops = tw.breakdown()["device_ops"]
    assert ops[0][0] == "jit_traverse" and len(ops) == 5


def test_trace_without_timed_stretch_is_refused():
    ps = planes()
    ps[1].lines[0].events = [e for e in ps[1].lines[0].events
                             if e.name not in SPANS]
    with pytest.raises(ValueError):
        tracing.from_planes(ps, SPANS)


def test_walk_table_work_depends_only_on_shapes():
    from lib import community

    import jax.numpy as jnp
    from repro.api import AssemblyPlan
    from repro.core.types import ReadSet

    work = run.load_module("work", "walk_tables")
    cfg = run.load_cell("phage_cami.s3000", ROOT)["config"]
    got = []
    for seed in (1, 2**33 + 7):
        s = community.sample(seed, 0, pairs=300, community=cfg["community"],
                             reads=cfg["reads"])
        reads = ReadSet(bases=jnp.asarray(s.bases),
                        lengths=jnp.asarray(s.lengths),
                        mate=jnp.asarray(s.mate), insert_size=s.insert_size)
        plan = AssemblyPlan.from_dataset(reads, (17, 21, 4),
                                         unique_rate=0.1)
        got.append(work.hbm_bytes(run.work_shapes(plan, s)))
    assert got[0] == got[1]
    R, L, cap = 600, 150, 1 << 17  # p2(600 reads * 134 windows)
    lanes = sum(R * (L - m + 1) for m in (13, 17, 21, 17, 21, 25, 17, 21, 25))
    assert got[0] == lanes * 10 + 9 * cap * 41


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "phage_cami.s3000", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_device_missing_from_peak_table_exits_nonzero(monkeypatch, capsys):
    import jax

    fake = NS(platform="tpu", device_kind="TPU v0 imaginary")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    rc = run.main(["--workload", "phage_cami.s3000", "--seed", "3",
                   "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and '"correct"' not in out.out
    assert "peaks.json" in out.err
    with pytest.raises(KeyError):
        run.device_peaks("TPU v0 imaginary")
    assert run.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_samples_repeat_from_the_seed():
    from lib import community

    cfg = run.load_cell("phage_cami.s3000", ROOT)["config"]
    kw = dict(pairs=2500, community=dict(cfg["community"],
                                         genome_len=[5000, 10000],
                                         abundance_sigma=1.0,
                                         pairs_per_genome=416.6667),
              reads=cfg["reads"])
    a = community.sample(2**31 + 5, 3, **kw)
    b = community.sample(2**31 + 5, 3, **kw)
    c = community.sample(2**31 + 5, 4, **kw)
    d = community.sample(7, 3, **kw)
    assert np.array_equal(a.bases, b.bases)
    assert not np.array_equal(a.bases, c.bases)
    assert a.bases.shape == (5000, 150) and len(a.genomes) == 6
    assert a.coverage.max() >= 10
    # another seed: the same sizes, other bases
    assert [len(g) for g in a.genomes] == [len(g) for g in d.genomes]
    assert np.array_equal(a.coverage, d.coverage)
    assert not np.array_equal(a.bases, d.bases)
