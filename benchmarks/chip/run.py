"""Run one cell of the on-chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload phage_cami.s2500 --seed 7 \
        --seconds 51 --trace 0

The cell, its configuration, its traffic and its per-layer metrics are
found by name: the workload in `BENCHMARK.json` names a configuration
(`configs/<config>.json`) and a traffic mix (`traffic/<traffic>.json`);
each per-layer metric is `layer_metrics/<name>.json`, read by the reader it
names (`readers/<reader>.py`).

The timed unit is one whole assembly, `Assembler(plan, Local()).assemble`,
of a fresh MGSim sample drawn from `--seed`, until its rendered scaffolds
are on the host.  Set-up generates the first sample, then runs one warm-up
assembly of the cell's shapes with JAX's compilation cache at a fixed path
in the checkout.  The window starts assemblies back to back while less
than `--seconds` of timed assembly have passed, and ends when the last one
finishes.  What only the check needs is done between timed stretches, with
the clock stopped: drawing and uploading the next sample, and copying each
round's contigs and the assembly's other outputs to the host.  Every
assembly of the window is then checked against the plain reference
(`lib/reference.py`).  With `--trace 1` the window runs under the JAX
profiler, and the per-layer metrics come from its trace of the timed
stretches.

Without a TPU, with fewer chips than the cell asks for, or on a device
missing from `peaks.json`, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()  # set-up is counted from here

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
WORK_DIR = os.path.join(ROOT, "experiments", "chip_bench")
CACHE_DIR = os.path.join(WORK_DIR, "jax_cache")
TRACE_DIR = os.path.join(WORK_DIR, "trace")

sys.path.insert(0, HERE)

from lib import community, reference  # noqa: E402


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The workload entry of `BENCHMARK.json`, with its configuration, its
    traffic and the specs of its metrics."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    for m in per_layer:
        m["spec"] = load_json(os.path.join(HERE, "layer_metrics",
                                           m["name"] + ".json"))
    return {
        "name": workload,
        "chips": cell["chips"],
        "config": load_json(os.path.join(root, config["file"])),
        "traffic": load_json(os.path.join(HERE, "traffic",
                                          cell["traffic"] + ".json")),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": per_layer,
    }


def load_module(kind: str, name: str):
    """`readers/<name>.py` or `work/<name>.py`, by file."""
    spec = importlib.util.spec_from_file_location(
        f"{kind}_{name}", os.path.join(HERE, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_peaks(kind: str) -> dict:
    """The published peaks of a device, from `peaks.json`; a device that is
    not in the table is an error, not a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


class CompileCounter:
    """Compiles, persistent-cache loads and traces JAX reports."""

    def __init__(self):
        import jax

        self.counts = {"compiles": 0, "cache_loads": 0, "traces": 0}
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1
            self.compile_s += secs
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.counts["traces"] += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_loads"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def _frame_contigs(gen):
    """The (contigs, alive) of the round the assembly generator just
    finished, from the innermost generator frame that holds them."""
    chain = []
    while gen is not None:
        chain.append(gen)
        gen = gen.gi_yieldfrom
    for g in reversed(chain):
        loc = g.gi_frame.f_locals if g.gi_frame is not None else {}
        if loc.get("contigs") is not None and loc.get("alive") is not None:
            return loc["contigs"], loc["alive"]
    raise RuntimeError("no round contigs in the assembly generator's frames")


def assemble(plan, reads, label: str, spans: dict):
    """One assembly: its timed seconds, and what the check reads, on the host.

    The clock runs from the assembly's start until its rendered scaffolds
    are on the host, and stops while the check's copies are made.  Each
    `next()` of `assemble_iter` runs inside a profiler annotation
    `<label>/<step>`, which `spans` maps to the stage event it produced;
    these annotations are the timed stretches of a trace.  At the event of
    every round but the last, the program has already read the round's
    statistics back, so its contigs are ready: they are copied to the host
    then, with the clock stopped (the final round's are in the result)."""
    import jax

    from repro.api import Assembler, Local

    gen = Assembler(plan, Local()).assemble_iter(reads)
    rounds, step, timed = [], 0, 0.0
    t = time.perf_counter()
    while True:
        name = f"{label}/{step}"
        with jax.profiler.TraceAnnotation(name):
            try:
                stage, info = next(gen)
            except StopIteration as stop:
                out = jax.block_until_ready(stop.value)
                seqs = jax.device_get(out["scaffold_seqs"]._asdict())
                spans[name] = "close_gaps"
                break
        spans[name] = f"{stage} k={info['k']}" if "k" in info else stage
        step += 1
        if stage == "contig_rounds" and info["k"] != plan.ks()[-1]:
            timed += time.perf_counter() - t
            with jax.profiler.TraceAnnotation(f"{label}/check"):
                contigs, alive = _frame_contigs(gen)
                rounds.append(_trim_contigs(dict(jax.device_get({
                    "bases": contigs.bases, "lengths": contigs.lengths,
                    "alive": alive}), k=info["k"])))
                del contigs, alive
            t = time.perf_counter()
    timed += time.perf_counter() - t
    with jax.profiler.TraceAnnotation(f"{label}/check"):
        host = jax.device_get({
            "bases": out["contigs"].bases, "lengths": out["contigs"].lengths,
            "alive": out["alive"], "suspended": out["suspended"],
            "alignments": out["alignments"]._asdict(),
            "scaffolds": out["scaffolds"]._asdict(),
        })
    host["seqs"] = seqs
    rounds.append(_trim_contigs({"k": plan.ks()[-1],
                                 "bases": host.pop("bases"),
                                 "lengths": host.pop("lengths"),
                                 "alive": host["alive"]}))
    overflow = dict(out["overflow"])
    for rnd, st in zip(rounds, out["stats"]):
        rnd["n_kmers"] = st.n_kmers
        overflow[f"round_k{st.k}"] = int(st.overflow)
        overflow[f"round_k{st.k}_route"] = int(st.route_overflow)
    host["rounds"] = rounds
    host["suspended"] = host["suspended"][:len(rounds[-1]["lengths"])]
    host["overflow"] = overflow
    # a scaffold that reached the plan's longest row was cut there
    overflow["scaffold_len"] = int(
        (seqs["lengths"] >= seqs["bases"].shape[1]).sum())
    n = int(seqs["n_scaffolds"])
    seqs["bases"] = seqs["bases"][:n, :int(seqs["lengths"][:n].max(
        initial=0))].copy()
    return timed, host


def upload(sample):
    """A sample's reads on the device, as the assembler takes them."""
    import jax
    import jax.numpy as jnp

    from repro.core.types import ReadSet

    return jax.block_until_ready(ReadSet(
        bases=jnp.asarray(sample.bases), lengths=jnp.asarray(sample.lengths),
        mate=jnp.asarray(sample.mate), insert_size=sample.insert_size))


def _trim_contigs(rnd: dict) -> dict:
    """A round's contig rows up to the last live one, cut to the longest,
    so the host keeps megabytes, not the plan's padded rows."""
    import numpy as np

    live = np.flatnonzero(rnd["alive"] & (rnd["lengths"] > 0))
    rows = int(live[-1]) + 1 if live.size else 0
    width = int(rnd["lengths"][live].max()) if live.size else 0
    return dict(rnd, bases=rnd["bases"][:rows, :width].copy(),
                lengths=rnd["lengths"][:rows], alive=rnd["alive"][:rows])


def check_plan(plan, g: dict) -> None:
    """The program runs as the configuration states its guarantees."""
    stated = {"min_count": plan.min_count,
              "contig_pseudo_weight": plan.contig_pseudo_weight,
              "min_link_support": plan.min_link_support,
              "gapped_align": plan.gapped_align,
              "align_seed_len": min(plan.k_max, 27)}
    for key, value in stated.items():
        if g[key] != value:
            raise ValueError(f"plan {key} = {value}, the configuration "
                             f"states {g[key]}")
    if plan.contig_pseudo_weight < plan.min_count:
        raise ValueError("contig k-mers would not enter the next round")


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device: dict, peaks: dict, warmup: bool = True) -> dict:
    """Set-up, window, reference check and metrics of one run.  Without
    `warmup` (readings of the checks in a process that has run the cell
    before) set-up skips the warm-up assembly."""
    import jax

    from repro.api import AssemblyPlan

    cfg, pairs = cell["config"], cell["traffic"]["pairs_per_sample"]
    g = cfg["guarantees"]

    def draw(i):
        return community.sample(seed, i, pairs=pairs,
                                community=cfg["community"],
                                reads=cfg["reads"])

    counter = CompileCounter()
    first = draw(0)
    reads = upload(first)
    asm = cfg["assembly"]
    plan = AssemblyPlan.from_dataset(reads, tuple(asm["k_range"]),
                                     unique_rate=asm["unique_rate"])
    check_plan(plan, g)
    if warmup:
        assemble(plan, reads, "warmup", {})
    del reads
    setup_s = time.perf_counter() - T_START
    before = counter.snapshot()
    print(f"setup: {setup_s:.3f} s; compile {counter.compile_s:.3f} s, "
          f"{before}; plan.bytes() {plan.bytes()}", flush=True)

    if trace:
        # host annotations only: the Python tracer would record every call
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    results, samples, spans, window_s = [], [], {}, 0.0
    t0 = time.perf_counter()
    while window_s < seconds:
        i = len(results) + 1
        samples.append(draw(i))
        reads = upload(samples[-1])
        timed, host = assemble(plan, reads, f"a{i}", spans)
        del reads
        window_s += timed
        results.append(host)
    wall_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    during = {k: v - before[k] for k, v in counter.snapshot().items()}
    print(f"window: {len(results)} assemblies in {window_s:.6f} s timed "
          f"({wall_s:.6f} s with the check's copies and the samples' "
          f"upload); inside the window {during}", flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")

    t_check = time.perf_counter()
    checks, per_asm = judge(results, samples, g)
    print(f"reference check: {len(results)} assemblies in "
          f"{time.perf_counter() - t_check:.3f} s", flush=True)
    deep = [f for r in per_asm for f in r["deep_fractions"]]
    bases = sum(s.read_bases for s in samples)
    line = {
        "correct": all(c["ok"] for c in checks.values()),
        "attempted": len(results),
        "failed": sum(1 for r in per_asm if not r["ok"]),
        "metrics": {},
        "device": dict(device, memory_peak_bytes=peak_bytes),
    }
    if trace:
        from lib import tracing

        tw = tracing.load(TRACE_DIR, spans)
        ctx = {"assemblies": len(results), "peaks": peaks,
               "shapes": work_shapes(plan, first),
               "work": lambda name: load_module("work", name)}
        for m in cell["per_layer"]:
            reader = load_module("readers", m["spec"]["reader"])
            value = reader.read(tw, m["spec"], ctx)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        line["device"].update(busy_s=tw.busy_s, window_s=tw.window_s)
        line["breakdown"] = tw.breakdown()
        print(f"trace: device time in modules no layer file matches "
              f"{tw.unmatched_s(cell['per_layer']):.6f} s; busy "
              f"{tw.busy_s:.6f} s of {tw.window_s:.6f} s", flush=True)
    else:
        e2e = {"read_bases_per_s": bases / window_s,
               "genome_fraction_10x": sum(deep) / len(deep) if deep else 0.0,
               "peak_hbm_bytes": peak_bytes,
               "setup_s": setup_s}
        for m in cell["end_to_end"]:
            if e2e.get(m["name"]) is not None:
                line["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                              "unit": m["unit"]}
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"],
                          "rule": c["rule"]} for k, c in checks.items()}
    return line


def work_shapes(plan, sample) -> dict:
    """The shapes a work function may read: reads and walk-table builds
    (one per round's local assembly, one for gap closing)."""
    ks = plan.ks()
    return {"reads": list(sample.bases.shape),
            "walk_capacity": plan.walk_capacity,
            "walk_table_builds": [list(plan.ladder(k))
                                  for k in ks + [ks[-1]]]}


def judge(results, samples, g: dict):
    """Each number compared, worst over the window's assemblies, beside its
    limit; and each assembly's own verdict."""
    limits = g["limits"]
    per_asm = []
    worst = {}
    for res, s in zip(results, samples):
        reads = {"bases": s.bases, "lengths": s.lengths, "mate": s.mate,
                 "insert_size": s.insert_size}
        nums = reference.check(res, reads, s.genomes, s.coverage, g)
        ok = True
        for name, (rule, limit) in limits.items():
            v = nums[name]
            ok &= v <= limit if rule == "<=" else v >= limit
            if name not in worst:
                worst[name] = v
            elif rule == "<=":
                worst[name] = max(worst[name], v)
            else:
                worst[name] = min(worst[name], v)
        nums["ok"] = ok
        per_asm.append(nums)
    checks = {}
    for name, (rule, limit) in limits.items():
        v = worst.get(name)
        ok = v is not None and (v <= limit if rule == "<=" else v >= limit)
        checks[name] = {"value": v, "limit": limit, "rule": rule, "ok": ok}
    return checks, per_asm


def use_compile_cache():
    """Keep every compiled program in the checkout's fixed cache directory,
    so that only a cell's first run there compiles."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    jax = use_compile_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}", flush=True)
    if device["platform"] != "tpu":
        print("error: JAX finds no TPU; this benchmark has no CPU fallback",
              file=sys.stderr)
        return 1
    if device["count"] < cell["chips"]:
        print(f"error: the cell asks for {cell['chips']} chips, JAX sees "
              f"{device['count']}", file=sys.stderr)
        return 1
    try:
        peaks = device_peaks(device["kind"])
    except KeyError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                    peaks)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['rule']} {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
