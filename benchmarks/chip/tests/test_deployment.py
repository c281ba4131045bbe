"""The configuration's deployment: a Mesh over the cell's chips, refusals
before set-up, the peak over the devices used, and a trace of four chips."""
import copy
import json
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace as NS

import pytest

from conftest import BENCH, ROOT

import run
from lib import tracing

# Time limit of the four-device run: about five times the 46 s it takes
# alone on 8 CPU cores (set-up, one Mesh(4) assembly, the reference
# check), so a stage that starts compiling per call fails with its output
# instead of running on.
MESH_RUN_TIMEOUT_S = 240


def deployed(cell, chips, **assembly):
    cell = copy.deepcopy(cell)
    cell["chips"] = chips
    cell["config"]["assembly"] = dict(
        {k: v for k, v in cell["config"]["assembly"].items()
         if k != "context"}, **assembly)
    return cell


def run_on_four_devices(cell):
    """`run.run_cell` of `cell` in an interpreter that sees 4 CPU host
    devices (XLA_FLAGS is read once, at JAX's import): its result line."""
    script = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{BENCH!r}, {os.path.join(ROOT, "src")!r}]
        import jax
        import run
        assert len(jax.devices()) == 4
        cell = json.loads({json.dumps(cell)!r})
        line = run.run_cell(cell, 2**31 + 11, 1e-3, False,
                            {{"platform": "cpu", "kind": "cpu",
                              "count": 4}}, {{}})
        print(json.dumps(line))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                       capture_output=True, text=True,
                       timeout=MESH_RUN_TIMEOUT_S)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-4000:]
    assert "Mesh over 4 of 4 devices" in p.stdout
    return json.loads(p.stdout.strip().splitlines()[-1])


def mesh_cell(tiny_cell, pairs):
    """The tiny configuration as `Mesh` over 4 chips, with eight genomes of
    1.5-2.5 kbp.

    Community and seed are chosen to miss an open fault of the program:
    `Mesh(4)` overflows its localization lanes on most small samples (on
    two genomes a sample, and at this shape on 2 of 4 seeds), and the
    overflow check then fails the run.  So these tests show that the
    harness drives and checks a `Mesh`, and are no evidence that
    `Mesh(4)` assembles soundly."""
    cell = deployed(tiny_cell, 4, context="Mesh")
    cell["config"]["community"].update(genome_len=[1500, 2500],
                                       pairs_per_genome=100)
    cell["traffic"] = {"pairs_per_sample": pairs}
    return cell


def test_mesh_cell_runs_correct_on_four_devices(tiny_cell):
    """Set-up, a window of one Mesh(4) assembly and the check, on a sample
    that does not overflow localization."""
    line = run_on_four_devices(mesh_cell(tiny_cell, 800))
    assert line["correct"], line["checks"]
    assert line["attempted"] == 1 and line["failed"] == 0


def test_mesh_padding_rows_are_not_checked_as_reads(tiny_cell):
    """1,602 reads pad to 1,604 rows over 4 shards: the check reads the
    alignment of each read, not of the padding.  This sample overflows
    localization: the run reports it and is not correct."""
    line = run_on_four_devices(mesh_cell(tiny_cell, 801))
    checks = line["checks"]
    for name in ("kmer_count_diff", "contig_kmers_unsolid",
                 "alignment_errors", "contigs_unplaced", "render_diff"):
        assert checks[name]["value"] == 0, (name, checks)
    assert checks["overflow"]["value"] > 0, checks
    assert not line["correct"]


@pytest.mark.parametrize("chips,assembly", [
    (4, {"context": "Local"}),
    (1, {"context": "Remote"}),
    (1, {}),
], ids=["local_4_chips", "unknown", "no_context"])
def test_deployment_not_stated_right_is_refused_before_setup(
        tiny_cell, monkeypatch, chips, assembly):
    def setup_started(*a, **kw):
        raise AssertionError("set-up started")

    monkeypatch.setattr(run.community, "sample", setup_started)
    monkeypatch.setattr(run, "use_compile_cache", setup_started)
    cell = deployed(tiny_cell, chips, **assembly)
    with pytest.raises(ValueError):
        run.run_cell(cell, 3, 1.0, False, {}, {})
    monkeypatch.setattr(run, "load_cell", lambda name: cell)
    assert run.main(["--workload", "any", "--seed", "3",
                     "--seconds", "1"]) != 0


@pytest.mark.parametrize("chips,assembly,want", [
    (1, {"context": "Local"}, "Local"),
    (4, {"context": "Mesh"}, "Mesh"),
    (1, {"context": "Mesh"}, "Mesh"),
])
def test_stated_deployment_is_taken(tiny_cell, chips, assembly, want):
    assert run.deployment(deployed(tiny_cell, chips, **assembly)) == want


def test_every_cell_of_the_benchmark_states_its_deployment():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        assert run.deployment(run.load_cell(w["name"], ROOT)) in run.CONTEXTS


def test_peak_is_the_fullest_device_used():
    dev = lambda peak: NS(memory_stats=lambda: None if peak is None
                          else {"peak_bytes_in_use": peak})
    assert run.peak_bytes([dev(7)]) == 7
    assert run.peak_bytes([dev(7), dev(None), dev(11), dev(3)]) == 11
    assert run.peak_bytes([dev(None)]) is None
    assert run.peak_bytes([dev(5), dev(9)][:1]) == 5


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def four_chip_planes():
    """Four TPU planes over one timed stretch [0, 1000): chip n runs
    `jit_traverse` for 100 (n + 1) and `jit__seed_index` for 200, apart;
    a non-TPU device plane and the check's copies fall outside."""
    planes = [NS(name=f"/device:TPU:{n}", lines=[NS(
        name="XLA Modules", events=[
            ev(f"jit_traverse({n})", 0, 100 * (n + 1)),
            ev(f"jit__seed_index({n})", 600, 200),
            ev("jit_convert_element_type(9)", 1100, 50),
        ])]) for n in range(4)]
    planes.append(NS(name="/device:CPU:0", lines=[NS(
        name="XLA Modules", events=[ev("jit_traverse(1)", 0, 1000)])]))
    planes.append(NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("a1/0", 0, 1000), ev("a1/check", 1000, 200)])]))
    return planes


def test_trace_of_four_chips_averages_over_their_planes():
    tw = tracing.from_planes(four_chip_planes(), {"a1/0": "align k=21"})
    assert len(tw.devices) == 4
    assert tw.window_s == pytest.approx(1000e-9)
    # chip n busy 100 (n + 1) + 200: mean 250 + 200
    assert tw.busy_s == pytest.approx(450e-9)
    assert tw.module_seconds(["jit_traverse"]) == pytest.approx(250e-9)
    assert tw.module_seconds(["jit__seed_index"]) == pytest.approx(200e-9)
    assert dict(tw.breakdown()["device_ops"]) == pytest.approx(
        {"jit_traverse": 250e-9, "jit__seed_index": 200e-9})
    ctx = {"assemblies": 1}
    layer = lambda name: json.load(open(os.path.join(
        BENCH, "layer_metrics", name + ".json")))
    read = lambda name: run.load_module(
        "readers", layer(name)["reader"]).read(tw, layer(name), ctx)
    assert read("alignment.device_s") == pytest.approx(200e-9)
    assert read("contig_graph.device_s") == pytest.approx(250e-9)
    assert read("device.idle_share") == pytest.approx(1 - 450 / 1000)
