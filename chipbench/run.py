"""Run one benchmark cell once: build, warm up, measure, check, print.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration is ``chipbench/configs/<config>.json``, its traffic mix
``chipbench/traffic/<traffic>.json`` (read by ``loadgen``), and each
per-layer metric is read by ``chipbench/metrics/<metric>.py``.  The
configuration names its own semantics: its data generator
(``"data": {"generator": "<g>", ...}``, ``chipbench/generators/<g>.py``)
and its reference (``"reference": "<r>"``, ``chipbench/references/<r>.py``).
Everything runs in this one process, which holds the cell's chips.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.

A generator gives:

* ``Spec(cfg)``: what the configuration says about its data, with
  ``.rows``, the corpus's row count;
* ``corpus(spec)``: the whole corpus (rows, dim), the same for the same
  configuration;
* ``build_key(spec)``: the raw key the index is built with;
* ``queries(spec, idx, *, warmup=False)``: queries number ``idx`` of the
  configuration's pool, (len(idx), dim); the warm-up pool is disjoint from
  the measured one.

A reference gives:

* ``compare(answers, x, q, *, rows, k, limits)``: judges served answers
  [(ids, distances) or None] for queries ``q`` against the exact answers
  over corpus ``x``; returns ``checks`` (each number beside its limit),
  ``correct`` and ``hits`` (true top-k ids found, per answer);
* ``malformed(ids, dists, rows, k)``: the count of answers that are not k
  distinct in-range ids with finite distances in ascending order.  A
  served distance is smaller for a nearer row under every metric;
* ``control_answers(x, q, k)``: (ids, distances) of the reference one
  precision lower, which ``compare`` must call not correct.

Nothing here assumes that ``x`` lives on the device; the harness only
hands it from the generator to the reference.

Set-up (``setup_s``) runs from process start to the window's start: data
generation on the device, ``Database.build``, warm-up calls of the served
path.  The window drives ``ServingEngine.serve``.  After it, the system is
freed and the configuration's reference judges the answers.  The last line
of standard output is the result; the last lines of standard error give
each number compared beside its limit.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
CACHE = ROOT / ".chipbench_cache"
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    pass


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_parts(man: dict, workload: str) -> dict:
    """Everything the cell names, found by name under chipbench/."""
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}")
    cell = cells[workload]
    from chipbench import loadgen
    config = json.loads((BENCH / "configs" / f"{cell['config']}.json")
                        .read_text())
    mix = loadgen.Mix.load(BENCH / "traffic" / f"{cell['traffic']}.json")
    e2e = [m for m in man["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in man["per_layer"]
             if workload in m.get("workloads", [workload])
             and m["moves"] in names]
    return {"cell": cell, "config": config, "mix": mix, "end_to_end": e2e,
            "per_layer": layer, **semantics(config)}


def semantics(config: dict) -> dict:
    """The generator and the reference that a configuration names."""
    return {"generator": load("generators", config["data"]["generator"]),
            "reference": load("references", config["reference"])}


def load(kind: str, name: str):
    """``chipbench/<kind>/<name>.py``, loaded by its path once a process."""
    key = f"chipbench_{kind}_{name}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, BENCH / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


def load_metric(name: str):
    return load("metrics", name)


def device_info(jax, chips: int, *, require_tpu: bool = True) -> dict:
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory(jax, chips: int, key: str) -> int | None:
    vals = [(d.memory_stats() or {}).get(key) for d in jax.devices()[:chips]]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


class CompileCounter:
    """Counts programs lowered while armed (none should be in the window)."""

    def __init__(self, jax):
        self.armed, self.count, self._jax = False, 0, jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event == LOWERING_EVENT:
            self.count += 1

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on)


class GcTimer:
    """Times the Python collector's passes while armed, by generation."""

    def __init__(self):
        self.armed, self.passes, self._t = False, [], None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.armed and self._t is not None:
            self.passes.append((info["generation"],
                                time.perf_counter() - self._t))

    def summary(self) -> dict:
        return {g: [sum(1 for p in self.passes if p[0] == g),
                    sum(p[1] for p in self.passes if p[0] == g)]
                for g in range(3)}

    def close(self) -> None:
        gc.callbacks.remove(self._on)


@contextlib.contextmanager
def _no_annotation(*_, **__):
    yield


def build(parts: dict, seed: int):
    """Data from the configuration's generator, ``Database.build``, the
    engine.  Returns (engine, data spec)."""
    import jax
    from repro.anns import Database, PipelineConfig, QueryPlan
    from repro.serving import ServingEngine

    cfg, mix, data = parts["config"], parts["mix"], parts["generator"]
    spec = data.Spec(cfg)
    t = time.perf_counter()
    x = jax.block_until_ready(data.corpus(spec))
    t_data = time.perf_counter() - t
    db = Database.build(data.build_key(spec), x,
                        PipelineConfig(**cfg["pipeline"]))
    jax.block_until_ready(db.index.x)
    del x
    log(f"setup data_s: {t_data}")
    log(f"setup build_s: {time.perf_counter() - t - t_data}")
    engine = ServingEngine(db, plan=QueryPlan(backend=mix.backend),
                           max_batch=mix.max_batch)
    return engine, spec


def run_cell(parts: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start: float | None = None,
             system=build, engine_hook=None) -> dict:
    """One run of a cell; returns the result record (see ``main``).
    ``system(parts, seed)`` makes what the window drives (the control puts
    the reference there); ``engine_hook`` may wrap it after set-up."""
    import jax
    from chipbench import loadgen, xtrace

    t_start = T0 if t_start is None else t_start
    cfg, mix, chips = parts["config"], parts["mix"], parts["cell"]["chips"]
    data, reference = parts["generator"], parts["reference"]
    device = device_info(jax, chips, require_tpu=require_tpu)
    counter, collector = CompileCounter(jax), GcTimer()
    annotate = jax.profiler.TraceAnnotation if trace else _no_annotation

    engine, spec = system(parts, seed)
    t = time.perf_counter()
    warm = lambda idx: data.queries(spec, idx, warmup=True)  # noqa
    loadgen.warm_up(engine, mix, warm, annotate)
    log(f"setup warmup_s: {time.perf_counter() - t}")
    qs = loadgen.Queries(lambda idx: data.queries(spec, idx))
    qs.get(loadgen.query_indices(mix, seed, mix.callers))
    gc.collect()
    in_use = memory(jax, chips, "bytes_in_use")
    if engine_hook is not None:
        engine = engine_hook(engine)

    trace_dir = CACHE / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    counter.armed = collector.armed = True
    with annotate(xtrace.WINDOW):
        win = loadgen.run_window(engine, mix, seed, seconds, qs, annotate)
    counter.armed = collector.armed = False
    counter.close()
    collector.close()
    if trace:
        jax.profiler.stop_trace()
    peak = memory(jax, chips, "peak_bytes_in_use")
    index = probe_index(engine, cfg) if trace else None

    # free the system under test before the reference takes the device
    del engine
    gc.collect()
    pos = loadgen.check_positions(mix, seed, win)
    answers = [win.answers[p] for p in pos]
    x = data.corpus(spec)
    q = loadgen.Queries(lambda idx: data.queries(spec, idx)).get(
        [win.qidx[p] for p in pos])
    k = cfg["pipeline"]["final_k"]
    limits = dict(cfg["limits"],
                  recall_at_10_min=cfg["guarantees"]["recall_at_10_min"])
    verdict = reference.compare(answers, x, q, rows=spec.rows, k=k,
                                limits=limits)
    del x
    all_bad = reference.malformed([a[0] if a else None for a in win.answers],
                                  [a[1] if a else None for a in win.answers],
                                  spec.rows, k)
    checks = verdict["checks"]
    checks["malformed_answers"]["value"] = all_bad
    checks["unanswered"] = {"value": win.failed, "op": "<=",
                            "limit": cfg["limits"]["unanswered"]}
    correct = (verdict["correct"] and all_bad <= limits["malformed_answers"]
               and win.failed <= cfg["limits"]["unanswered"])

    # the recall set leads the judged positions
    n_rec = min(mix.recall_set, len(pos))
    recall = sum(verdict["hits"][:n_rec]) / (n_rec * k)

    lat = win.latencies_ms()
    values = {
        "qps": win.qps(),
        "p95_ms": loadgen.percentile(lat, 95),
        "recall_at_10": recall,
        "hbm_bytes_per_row": in_use / spec.rows if in_use else None,
        "setup_s": setup_s,
    }
    info = {"p50_ms": loadgen.percentile(lat, 50), "latency_samples":
            len(lat), "calls": len(win.sends), "window_s":
            win.returns[-1] - win.sends[0], "bytes_in_use": in_use,
            "peak_bytes_in_use": peak, "window_compiles": counter.count,
            "window_gc": collector.summary(),
            "call_s": [r - s for s, r in zip(win.sends, win.returns)],
            "gap_s": [s - r for s, r in zip(win.sends[1:], win.returns)]}

    metrics, extra = {}, {}
    if not trace:
        for m in parts["end_to_end"]:
            v = values[m["name"]]
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        path = xtrace.find(str(trace_dir))
        tr = xtrace.load(path)
        lo, hi = tr.window()
        ctx = types.SimpleNamespace(
            trace=tr, lo=lo, hi=hi, batches=win.batches, queries=qs.get,
            index=index, config=cfg,
            peak=peaks(device["kind"], require_tpu), notes={})
        for m in parts["per_layer"]:
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        n_dev = max(len(tr.devices), 1)
        device["busy_s"] = sum(xtrace.busy_ns(d, lo, hi)
                               for d in tr.devices) / n_dev / 1e9
        device["window_s"] = (hi - lo) / 1e9
        extra["breakdown"] = {"device_ops": xtrace.top_ops(tr, lo, hi),
                              "idle_gaps": xtrace.idle_gaps(tr, lo, hi)}
        info.update(ctx.notes)
    device["memory_peak_bytes"] = peak
    return {"correct": bool(correct), "attempted": win.attempted,
            "failed": win.failed, "metrics": metrics, "device": device,
            **extra, "info": info, "checks": checks, "window": win}


def probe_index(engine, cfg: dict) -> dict:
    """What a reader needs to count the candidates a query's probe reaches:
    the IVF centroids, each list's length, and ``nprobe``."""
    import numpy as np
    ivf = engine.db.index.ivf
    return {"centroids": np.asarray(ivf.centroids, np.float64),
            "list_len": np.asarray(ivf.list_len, np.int64),
            "nprobe": int(cfg["pipeline"]["nprobe"])}


def peaks(kind: str, require: bool = True) -> dict | None:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        if require:
            raise KeyError(f"device kind {kind!r} is not in peaks.json")
        return None
    return table[kind]


def report(result: dict) -> None:
    result.pop("window", None)
    info = result.pop("info")
    checks = result.pop("checks")
    for k, v in info.items():
        log(f"{k}: {v}")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} {c['op']} {c['limit']}")
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)


def configure() -> None:
    """Import paths, and JAX's compile cache in the checkout at a fixed path
    (whatever cache directory the environment names), caching every
    program.  Call before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    # the script's own directory is not a package root
    sys.path[:] = [p for p in sys.path if pathlib.Path(p or ".").resolve()
                   != BENCH]
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--corpus-seed", type=int, default=None,
                    help="build and judge on another corpus and query pool "
                    "than the configuration's own (proof runs only)")
    args = ap.parse_args(argv)

    configure()
    import jax
    parts = cell_parts(manifest(), args.workload)
    if args.corpus_seed is not None:
        parts["config"]["data"]["corpus_seed"] = args.corpus_seed
    try:
        device_info(jax, parts["cell"]["chips"])
        result = run_cell(parts, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"chipbench: {e}")
        return 2
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
