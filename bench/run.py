#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
the cell's configuration in ``bench/configs/<config>.json``, whose
``generator`` names ``bench/generators/<generator>.py``; its traffic
mix in ``bench/traffic/<traffic>.json`` (read by ``bench/loadgen.py``);
and each per-layer metric's reader in ``bench/metrics/<metric>.py``.
A metric's name may carry a tag after a last dot that only tells apart
entries with their own ``moves`` or bound (``solves_per_s.hpcg``,
``device_idle_pct.stream``): where no reader or window number of the
full name exists, the name without its tag is used.

A run builds the configuration's operator, warms every program the
window will use (set-up, ``setup_s``), drives the traffic for
``--seconds``, reads the device's peak memory, frees the program's
state, and checks every answer of the window against the float64
reference (``bench/reference.py``).  ``--trace 1`` records the window
with the profiler and the program's spans, and reports the per-layer
metrics instead of the end-to-end ones, with a ``breakdown`` of the
device's time whose idle gaps are named by the innermost span open in
each, the program's or the benchmark's (:func:`breakdown`).  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` when traced, and
``check`` last); the last lines of standard error give each number
compared beside its limit.

It exits non-zero, and prints no result, without a TPU or with fewer
chips than the cell asks for, and outside a checkout of the repository.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache and the traces, inside the checkout
STATE = ROOT / ".bench"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoDevice(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def cell_of(spec: dict, workload: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")


def config_of(cell: dict) -> dict:
    """The configuration a cell of ``BENCHMARK.json`` names, as its file
    ``bench/configs/<config>.json`` holds it."""
    return load_json(BENCH / "configs" / f"{cell['config']}.json")


def traffic_of(cell: dict) -> dict:
    """The traffic mix a cell names, ``bench/traffic/<traffic>.json``."""
    return load_json(BENCH / "traffic" / f"{cell['traffic']}.json")


def untagged(name: str, have) -> str:
    """``name``, or the longest of its dotted prefixes, that ``have``
    holds (``m1.stream_bytes_per_nnz.hpcg`` -> ``m1.stream_bytes_per_nnz``)."""
    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        if ".".join(parts[:k]) in have:
            return ".".join(parts[:k])
    raise KeyError(name)


def reader(name: str) -> types.ModuleType:
    """The per-layer metric's reader, ``bench/metrics/<name>.py``, or
    that of the name without its tag."""
    files = {p.stem for p in (BENCH / "metrics").glob("*.py")}
    name = untagged(name, files)
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metrics_of(spec: dict, cell: str, kind: str) -> list:
    """The cell's end-to-end or per-layer metric entries."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"run.py: needs a TPU; JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"run.py: the cell asks for {chips} chips; JAX "
                       f"found {len(devs)}")
    return devs


class CompileCount:
    """Backend compiles, and their seconds, while active (a persistent
    cache hit counts none)."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, for every program however quick to compile; program code
    that reads ``JAX_COMPILATION_CACHE_DIR`` finds the same path."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(STATE / "jax_cache")
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def peak_memory(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def setup(workload: str, *, require_tpu: bool = True, spec: dict = None,
          overrides: dict = None) -> types.SimpleNamespace:
    """Find the cell's files, the devices and the compile cache; build
    the configuration's operator and the traffic's loop around the
    program, and warm every program the window will run.

    ``spec`` stands in for ``BENCHMARK.json``; ``overrides`` replaces
    entries of the configuration (``{"cfg": {...}}``, one level deep)
    and of the traffic mix (``{"traffic": {...}}``).  The tests use them
    to drive a small cell on the CPU, and ``bench/readings.py`` to run
    the control.
    """
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"run.py: no checkout of the program around {ROOT}")
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cell = cell_of(spec, workload)
    cfg = config_of(cell)
    traffic = traffic_of(cell)
    traffic.update((overrides or {}).get("traffic", {}))

    for path in (ROOT, ROOT / "src"):
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))
    import jax
    devs = devices_for(cell["chips"], require_tpu)[:cell["chips"]]
    jax.config.update("jax_enable_x64", False)
    if require_tpu:
        enable_cache()
    from bench import loadgen, systems
    cfg = systems.overridden(cfg, (overrides or {}).get("cfg", {}))

    compiles = CompileCount()
    t0 = time.perf_counter()
    syst = systems.Systems(cfg)
    t1 = time.perf_counter()
    loop = loadgen.LOOPS[traffic["loop"]](cfg, traffic, syst, cfg["solver"])
    loop.warm()
    compiles.close()
    say(f"set-up: devices and imports {t0 - T_START:.3f} s, operator "
        f"{t1 - t0:.3f} s, warm-up {time.perf_counter() - t1:.3f} s "
        f"(backend compiles {compiles.seconds:.3f} s)")
    return types.SimpleNamespace(spec=spec, cell=cell, cfg=cfg,
                                 traffic=traffic, devs=devs, a=syst.a,
                                 loop=loop, compiles=compiles)


def breakdown(view) -> dict:
    """A traced run's ``breakdown``: the device operations that took most
    time and the longest idle gaps, named by the program's spans and
    scopes (``bench/programtrace.py``).  Where the program recorded none,
    that naming is ``bench/tracefile.py``'s: the benchmark's spans alone."""
    from bench import programtrace
    named = programtrace.view_of(view).trace
    return {"device_ops": named["device_ops"],
            "idle_gaps": named["idle_gaps"]}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START, **kw) -> dict:
    """One run of one cell; returns the result object (see module doc).
    ``kw`` goes to :func:`setup`."""
    built = setup(workload, **kw)
    import jax
    from bench import loadgen, programtrace, reference, roofline, tracefile
    spec, cfg, devs, a = built.spec, built.cfg, built.devs, built.a
    loop = built.loop
    kind = "per_layer" if trace else "end_to_end"
    wanted = metrics_of(spec, workload, kind)
    readers = {m["name"]: reader(m["name"]) for m in wanted} if trace else {}
    if trace:
        programtrace.arm()
    kernels = sorted({k for r in readers.values()
                      for k in getattr(r, "KERNELS", ())})
    loop.prepare(seed, seconds)

    spans = loadgen.Spans(traced=trace)
    trace_dir = STATE / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    window_compiles = CompileCount()
    setup_s = time.perf_counter() - t_start
    with spans("window"):
        win = loop.window(seed, seconds, spans)
    window_compiles.close()
    if trace:
        jax.profiler.stop_trace()
    memory_peak = peak_memory(devs)
    loop.close()
    del loop, built.loop
    gc.collect()

    limit = cfg["check"]["max_true_rel_residual"]
    check = reference.Check(math.inf if limit is None else limit)
    for ans in win.answers:
        check.add(a, ans.b, ans.x, ans.status)
    check.missing(win.missing)

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    out = {"correct": check.correct and limit is not None,
           "attempted": win.attempted, "failed": check.failed}
    view = types.SimpleNamespace(win=win, spans=spans, a=a, cfg=cfg,
                                 trace=None, peaks=None)
    if trace:
        view.trace = tracefile.summarize(tracefile.read_events(
            str(trace_dir)), kernels=kernels)
        view.peaks = roofline.peaks_of(dev.device_kind)
        device.update(busy_s=view.trace["busy_s"],
                      window_s=view.trace["window_s"])
        values = {name: r.read(view) for name, r in readers.items()}
    else:
        have = {**loadgen.end_to_end(win), "setup_s": setup_s}
        try:
            values = {m["name"]: have[untagged(m["name"], have)]
                      for m in wanted}
        except KeyError as e:
            raise RuntimeError(f"the window gave no {e}") from None
    out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                  "unit": m["unit"]}
                      for m in wanted if values.get(m["name"]) is not None}
    out["device"] = device
    if trace:
        out["breakdown"] = breakdown(view)
    out["window_compiles"] = window_compiles.count
    out["check"] = check.lines()

    say(f"cell {workload} seed {seed}: setup {setup_s:.3f} s, window "
        f"{win.seconds:.3f} s, {window_compiles.count} compiles in the window")
    if win.calls:
        secs = sorted(c["seconds"] for c in win.calls)
        its = [i for c in win.calls for i in c["iterations"]]
        say(f"calls {len(win.calls)}: seconds min {secs[0]:.4f} median "
            f"{secs[len(secs) // 2]:.4f} max {secs[-1]:.4f}; iterations "
            f"min {min(its)} max {max(its)} mean {sum(its) / len(its):.2f}")
    if win.latencies:
        late = sorted(win.lateness) or [0.0]
        say(f"requests {win.attempted}, missing {win.missing}; generator "
            f"lateness on waking: median {late[len(late) // 2]:.6f} s, "
            f"max {late[-1]:.6f} s over {len(win.lateness)} waits")
    for name, secs in sorted(spans.seconds.items()):
        secs = sorted(secs)
        say(f"span {name}: {len(secs)} x, median {secs[len(secs) // 2]:.4f} "
            f"s, longest {[round(x, 4) for x in secs[-3:]]} s")
    say(f"counters over the window: {win.counters}")
    say(f"check: {check.checked} answers against the float64 reference")
    say(f"check residual {check.worst!r} limit {check.limit!r}")
    say(f"check unconverged {check.unconverged} limit 0")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoDevice as e:
        say(e)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
