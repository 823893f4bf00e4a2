#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process a run. Set-up (import, weights from the seed, every program
the window will use warmed through the engine the window will drive) is
timed apart as ``setup_s``; then the window offers the cell's traffic to
the system through its normal entry and nothing compiles. ``--trace 0``
prints the cell's end-to-end metrics; ``--trace 1`` runs the same window
under the profiler and prints its per-layer metrics, ``busy_s`` /
``window_s`` and a ``breakdown``. After the window the program's state is
freed and the plain reference judges a seeded sample of what was served.
The last line of standard output is the result; a run that finds no TPU,
too few chips or a device kind without published peaks exits non-zero
and prints none.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import cells, correct, stats, trace as tr, traffic  # noqa: E402

OUT_DIR = os.path.join(ROOT, ".perfbench")      # traces; inside the checkout


def log(msg: str) -> None:
    """Progress goes to standard error: standard output carries the result
    line and nothing else."""
    print(f"[perfbench +{time.monotonic() - T_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


def require_chips(cell) -> list:
    """The devices this cell runs on; no fallback of any kind."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"perfbench: no TPU (platform {devs[0].platform!r}); device "
            f"metrics are measured on the chip only")
    if len(devs) < cell.chips:
        raise SystemExit(
            f"perfbench: {cell.name} needs {cell.chips} chips, found {len(devs)}")
    used = devs[: cell.chips]
    cells.peaks(used[0].device_kind)
    return used


def device_report(used) -> tuple[dict, object]:
    """The ``device`` entry of the result, and the fullest device."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
             for d in used]
    fullest = max(range(len(used)), key=lambda i: peaks[i])
    return {
        "platform": used[0].platform, "kind": used[0].device_kind,
        "count": len(used), "memory_peak_bytes": peaks[fullest] or None,
    }, used[fullest]


def open_cell(cell):
    """``(reference, adapter, traffic parameters)`` of a cell, by the
    names in its configuration's file."""
    config = cell.config
    return (cells.load_module("references", config["reference"]),
            cells.load_module("programs", config["program"]),
            traffic.load(cell.traffic_path))


class Compiles:
    """Counts backend compilations (JAX's own monitoring event): there
    must be none inside the window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1


class Ticker:
    """A thread that sleeps ``STEP`` seconds at a time and keeps its
    longest oversleep: when the whole machine stands still for a while
    (a one-chip machine shares its host), this shows it, and a run that
    reads far off can be told from a slow program. Diagnostic only: it
    goes to standard error, no metric reads it."""

    STEP = 0.05

    def __init__(self):
        import threading

        self.worst, self._stop = 0.0, threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        last = time.monotonic()
        while not self._stop.wait(self.STEP):
            now = time.monotonic()
            self.worst = max(self.worst, now - last - self.STEP)
            last = now

    def close(self) -> float:
        self._stop.set()
        self._thread.join()
        return self.worst


class Run:
    """What a metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def kernel(self, name: str):
        return cells.load_module("kernels", name)

    def modules(self, kind: str) -> tr.Events:
        """Executions of one of the program's compiled programs on the
        fullest device, inside the window."""
        ev = self.trace.line(self.plane, tr.MODULES).matching(self.programs[kind])
        return ev.within(*self.window)

    def ops(self) -> tr.Events:
        return self.trace.line(self.plane, tr.OPS).within(*self.window)


def read_metrics(names: list, run: Run) -> dict:
    out = {}
    for name in names:
        if name == "setup_s":
            value, unit = run.setup_s, "s"
        else:
            mod = cells.load_module("metrics", name)
            value, unit = mod.read(run), mod.UNIT
        if value is None or not math.isfinite(value):
            log(f"metric {name}: nothing to read")
            continue
        out[name] = {"value": float(value), "unit": unit}
    return out


def breakdown(run: Run) -> dict:
    ops = run.ops()
    busy = tr.busy_intervals(ops)
    return {
        # one program holds thousands of ops: grouped by opcode and shape
        "device_ops": [[f"{n} x{c}", s]
                       for n, c, s in ops.by_name(tr.kind_of)[:10]],
        "idle_gaps": tr.idle_gaps(busy, run.window, run.trace.host_spans()),
    }


def run_cell(cell, seed: int, seconds: float, trace: bool, used: list,
             tamper=None, keep_trace: bool = False) -> dict:
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = Compiles()
    config = cell.config
    reference, adapter, spec = open_cell(cell)
    reqs = traffic.generate(spec, config["sizes"]["vocab"], seed, seconds)
    offered = traffic.work(reqs)
    log(f"{cell.name}: seed {seed}, {offered['requests']} requests, "
        f"{offered['prompt_tokens']} prompt and {offered['output_tokens']} "
        f"output tokens offered over {seconds} s")

    system = adapter.System(config, reference, used, seed)
    log(f"weights and engine in place; compile cache {system.cache_dir}")
    if tamper is not None:
        tamper(system)
    buckets = system.warm(reqs)
    log(f"warmed prompt buckets {buckets} and the decode step "
        f"({compiles.n} compilations so far)")
    weight_bytes = system.weight_bytes_per_device()
    prefill_rows = system.prefill_rows(reqs)
    trace_dir = os.path.join(OUT_DIR, "trace", cell.name)
    if trace:
        system.annotate()
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    n_compiled = compiles.n
    setup_s = time.monotonic() - T_START
    ticker = Ticker()
    records, t_open = system.serve(reqs)
    wall = time.monotonic() - T_START - setup_s
    overslept = ticker.close()
    in_window = compiles.n - n_compiled
    if trace:
        jax.profiler.stop_trace()
    device, fullest = device_report(used)
    flips = system.health_flips()
    failed = sum(not r.ok for r in records)
    log(f"window closed after {wall:.2f} s: {len(records) - failed} of "
        f"{len(records)} requests finished, {in_window} compilations inside "
        f"it, peak {device['memory_peak_bytes']} bytes on the fullest chip; "
        f"a thread sleeping {Ticker.STEP} s at a time overslept by at most "
        f"{overslept * 1e3:.0f} ms")

    run = Run(
        cell=cell, config=config, sizes=config["sizes"], spec=spec,
        records=records, t_open=t_open, seconds=seconds, setup_s=setup_s,
        chips=cell.chips, weight_bytes=weight_bytes, prefill_rows=prefill_rows,
        peaks=cells.peaks(device["kind"]) if device["platform"] == "tpu" else {},
        programs=adapter.PROGRAMS, trace=None,
    )
    result = {}
    if trace:
        run.trace = tr.load_xplane(tr.find_xplane(trace_dir))
        run.plane = f"/device:TPU:{fullest.id}"
        run.window = run.trace.window()
        busy = [tr.busy_s(run.trace.line(f"/device:TPU:{d.id}", tr.OPS)
                          .within(*run.window)) for d in used]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = (run.window[1] - run.window[0]) / 1e9
        metrics = read_metrics(cell.per_layer, run)
        result["breakdown"] = breakdown(run)
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = read_metrics(cell.end_to_end, run)
    ttft = sorted(stats.ttft_ms(records))
    log(f"samples: {len(records)} requests, "
        f"{sum(len(r.tokens) for r in records)} tokens; first token after "
        f"mean {stats.mean(ttft):.1f}, median {stats.percentile(ttft, 50):.1f}, "
        f"p90 {stats.percentile(ttft, 90):.1f}, at most {ttft[-1]:.1f} ms")

    # the program's state goes before the reference comes: the peak above
    # is the program's, and the reference gets the room
    prompts = {r.uid: r.prompt for r in reqs}
    system.free()
    del system
    t_ref = time.monotonic()
    dims = correct.shape(spec, int(spec.get("check_requests", 4)))
    picked = correct.sample(records, seed, dims[0])
    readings = {"failed": failed, "health_flips": flips}
    if picked:
        readings.update(correct.judge(
            reference, config["sizes"], seed, picked, prompts, dims, used))
    ok, numbers = correct.verdict(readings, config["limits"])
    log(f"reference took {time.monotonic() - t_ref:.2f} s over "
        f"{readings.get('requests_compared', 0)} requests, "
        f"{readings.get('tokens_compared', 0)} tokens; exact-argmax share "
        f"{readings.get('exact_share')}")
    result = {
        "correct": bool(ok), "attempted": len(records), "failed": failed,
        "metrics": metrics, "device": device, **result,
        "compiles_in_window": in_window, "numbers": numbers,
    }
    return result


def main(argv=None, *, devices=None, bench=None, tamper=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.Cell(bench or cells.benchmark(), args.workload)
    used = devices(cell) if devices is not None else require_chips(cell)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), used,
                      tamper=tamper)
    for name, (value, limit) in result["numbers"].items():
        print(f"perfbench compared {name}: {value} (limit {limit})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
