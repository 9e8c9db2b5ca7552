"""One run of one benchmark cell: set-up, measured window, check, result.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* the cell (``workloads`` entry) names a configuration and a traffic mix;
* the configuration's ``file`` is its deployment file; the module beside
  it with the same stem (``configs/<name>.py``) generates its tables from
  the seed;
* the traffic mix is ``traffic/<name>.json``, data driven by the
  generator its ``kind`` names, ``kinds/<kind>.py`` (see :mod:`loops`);
* each aggregate of a mix is folded by ``aggregates/<kind>.py``;
* each per-layer metric is read by ``metrics/<name>.py``.

So a cell, a configuration, a traffic kind, an aggregate or a metric is
added by adding files and entries; no file here changes.  The program under test is imported from
``<checkout>/src`` only.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


class BenchmarkError(RuntimeError):
    """The cell cannot run here: missing files, no chip, bad entries."""


def import_file(path: Path, name: str):
    if not path.is_file():
        raise BenchmarkError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell's entries and files, found by name from BENCHMARK.json."""
    bench_file = root / "BENCHMARK.json"
    if not bench_file.is_file():
        raise BenchmarkError(f"no {bench_file}")
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    cfg_file = root / entry["file"]
    if not cfg_file.is_file():
        raise BenchmarkError(f"no configuration file {cfg_file}")
    bench_dir = cfg_file.parents[1]
    mix_file = bench_dir / "traffic" / f"{cell['traffic']}.json"
    if not mix_file.is_file():
        raise BenchmarkError(f"no traffic mix {mix_file}")
    mix = json.loads(mix_file.read_text())
    kind_file = bench_dir / "kinds" / f"{mix['kind']}.py"
    if not kind_file.is_file():
        raise BenchmarkError(f"no traffic kind {kind_file}")
    return SimpleNamespace(
        bench=bench,
        cell=cell,
        cfg=json.loads(cfg_file.read_text()),
        cfg_mod=import_file(cfg_file.with_suffix(".py"), f"config_{entry['name']}"),
        mix=mix,
        kind_file=kind_file,
        bench_dir=bench_dir,
        end_to_end=[
            m for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])
        ],
        per_layer=[
            m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
        ],
    )


def import_program(root: Path):
    """Put ``<root>/src`` first on the path; the program lives there only."""
    src = root / "src"
    if not (src / "repro").is_dir():
        raise BenchmarkError(f"the program is not in this checkout ({src})")
    sys.path.insert(0, str(src))


def build_query(query: dict, aggs: dict, engine: str = "jax",
                bench_dir: Path = BENCH_DIR):
    """The program's ``Q`` for a deployment's query and a mix's aggregates."""
    from repro.api.builder import Q

    import reference

    q = Q.over(*(e if isinstance(e, str) else tuple(e) for e in query["over"]))
    for alias, mapping in query.get("renames", {}).items():
        q = q.rename(alias, **mapping)
    aggs_q = {
        n: reference.aggregate(kind, bench_dir).program(*column)
        for n, (kind, *column) in aggs.items()
    }
    return q.group_by(*query["group_by"]).agg(**aggs_q).engine(engine)


class Spans:
    """Host spans by name, on the host clock, and in the profiler's trace
    when one is being taken."""

    def __init__(self):
        self.seconds: dict[str, float] = {}
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        t = time.perf_counter()
        with ann:
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + time.perf_counter() - t


class CompileCounter:
    """Backend compiles and their seconds, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count, self.seconds = 0, 0.0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs


def _device_info() -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max((p for p in peaks if p is not None), default=None),
    }


def run(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    root: Path = ROOT,
    require_chip: bool = True,
    out=sys.stdout,
    err=sys.stderr,
) -> int:
    """Run the cell once; print the result line on ``out``.  Returns the
    exit code.  ``require_chip=False`` skips only the look for the chip
    (the tests drive the rest of a run on the CPU)."""
    try:
        spec = load_cell(workload, root)
        import jax

        devs = jax.devices()
        if require_chip and (
            devs[0].platform != "tpu" or len(devs) < spec.cell["chips"]
        ):
            raise BenchmarkError(
                f"cell {workload!r} needs {spec.cell['chips']} TPU chip(s); JAX "
                f"finds {len(devs)} {devs[0].platform} device(s)"
            )
        import_program(root)
    except BenchmarkError as e:
        print(f"run_cell: {e}", file=err)
        return 1

    from jax import monitoring

    sys.path.insert(0, str(spec.bench_dir))
    import check
    import tracing

    from repro.relational.relation import Database
    from repro.serve.server import JoinAggServer

    if require_chip:
        from repro.launch.cache import enable_compile_cache

        # the checkout's own cache, whatever the environment names: the
        # path is part of the cache's key, and two checkouts share nothing
        cache_dir = str(root / ".cache" / "jax")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        enable_compile_cache()
    compiles = CompileCounter()
    monitoring.register_event_duration_secs_listener(compiles)
    spans = Spans()
    trace_dir = server = None
    try:
        # ---- set-up: data, server, plan, warm-up ------------------------
        tables = spec.cfg_mod.generate(spec.cfg, seed)
        server = JoinAggServer(Database.from_mapping(tables))
        query = build_query(spec.cfg["query"], spec.mix["aggs"], bench_dir=spec.bench_dir)
        with spans("plan"):
            server.plan_cache.lookup(
                query, server.db, server.generation, server.stats_generation
            )
        kind = import_file(spec.kind_file, f"kind_{spec.mix['kind']}")
        loop = kind.make(spec.mix, SimpleNamespace(
            server=server, query=query, spec=spec, tables=tables, seed=seed
        ))
        loop.warm()
        setup_s = time.perf_counter() - t_start
        setup_compiles = (compiles.count, compiles.seconds)

        # ---- measured window ------------------------------------------
        if trace:
            trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
            spans.tracing = True
            jax.profiler.start_trace(
                str(trace_dir), profiler_options=tracing.profile_options()
            )
        try:
            with spans("window"):
                loop.window(seconds, spans)
        finally:
            if trace:
                jax.profiler.stop_trace()
                spans.tracing = False
        window_compiles = compiles.count - setup_compiles[0]
        device = _device_info()
    finally:
        monitoring.unregister_event_duration_listener(compiles)
        if server is not None:
            server.close()

    # ---- after the window: trace reduction, reference, check ------------
    summary = None
    if trace:
        summary = tracing.summarize(
            tracing.load(trace_dir, spans=("window", loop.span_name))
        )
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = summary.busy_s, summary.window_s
    result = check.check(spec, loop)
    del loop, server

    if trace:
        ctx = SimpleNamespace(
            spans=spans.seconds,
            compile_s=setup_compiles[1],
            trace=summary,
            tables=tables,
            query=spec.cfg["query"],
            aggs=spec.mix["aggs"],
            completed=result.completed,
            device_kind=device["kind"],
            bench_dir=spec.bench_dir,
        )
        metrics = {}
        for m in spec.per_layer:
            reader = import_file(
                spec.bench_dir / "metrics" / f"{m['name']}.py", f"metric_{m['name']}"
            )
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured = dict(result.end_to_end, setup_s=setup_s)
        metrics = {
            m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
            for m in spec.end_to_end
        }
    if window_compiles:
        print(f"run_cell: {window_compiles} compile(s) inside the window", file=err)
    print(
        f"run_cell: {workload} seed {seed}: {result.attempted} attempted, "
        f"{result.failed} failed; set-up {setup_s:.3f} s of which plan "
        f"{spans.seconds['plan']:.3f} s and backend compile {setup_compiles[1]:.3f} s",
        file=err,
    )
    for name, c in result.checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=err)
    line = {
        "correct": result.correct,
        "attempted": result.attempted,
        "failed": result.failed,
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        line["breakdown"] = summary.breakdown
    line["checks"] = result.checks
    print(json.dumps(line, allow_nan=False), file=out, flush=True)
    return 0
