#!/usr/bin/env python3
"""Runs one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is looked up by name in BENCHMARK.json at the root of the
checkout (spec.py). The run makes its data from the seed, builds and warms
the program (set-up), runs the cell's job for `--seconds` (the window),
then compares what the window produced with the plain reference
(check.py). With `--trace 0` it reports the cell's end-to-end metrics;
with `--trace 1` it traces the window with the profiler and reports the
per-layer metrics, the device's busy time and a breakdown. The last line
of standard output is one JSON object; the numbers compared, each beside
its limit, are its last key and the last lines of standard error.

It needs a TPU: with none, or fewer chips than the cell asks for, it exits
with code 1 and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import spec  # noqa: E402

ROOT = spec.repo_root(HERE)
sys.path.insert(0, os.path.join(ROOT, 'src'))
TRACE_DIR = os.path.join(ROOT, '.bench_trace')

@dataclasses.dataclass
class Context:
    """What a metric's `read(ctx)` may look at."""
    job: object
    window: object
    setup_s: float
    trace: dict | None

    @staticmethod
    def time_ms(fn, min_seconds: float = 0.3, samples: int = 3) -> float:
        """Median ms per call of `fn` (results waited for), each sample
        repeating it for at least `min_seconds`; one untimed call first."""
        import jax
        jax.block_until_ready(fn())
        per = []
        for _ in range(samples):
            n, t0 = 0, time.perf_counter()
            while True:
                jax.block_until_ready(fn())
                n += 1
                dt = time.perf_counter() - t0
                if dt >= min_seconds:
                    break
            per.append(1e3 * dt / n)
        return statistics.median(per)


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             devices) -> dict:
    """One run of `cell`; returns the result object."""
    import jax
    import check
    import device
    import job as job_mod
    import devtrace

    cfg = cell.config
    jax.config.update('jax_default_matmul_precision',
                      cfg['matmul_precision'])
    clock = device.CompileClock() if not hasattr(run_cell, 'clock') \
        else run_cell.clock
    run_cell.clock = clock

    t0 = time.perf_counter()
    c0 = clock.seconds
    job = job_mod.make(cfg, cell.traffic, seed)
    job.setup()
    setup_s = time.perf_counter() - t0
    log(f'[setup] {setup_s:.3f} s, {clock.seconds - c0:.3f} s compile')
    # The compiled step's static layout (for CSR data: its kind and whether
    # every row has the same length), where the job built its oracle.
    signature = getattr(job.oracle, 'step_signature', None)
    if callable(signature):
        log(f'[oracle] {type(job.oracle).__name__} step signature '
            f'{signature()}')

    n_compiles = clock.count
    reduced = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            win = job.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_compiles = clock.count - n_compiles
    mem = device.memory_peak_bytes(devices)
    log(f'[window] {win.seconds:.3f} s, {win.counts}, '
        f'{window_compiles} compiles')

    metrics = {}
    if trace:
        reduced = devtrace.reduce(devtrace.load(
            devtrace.find_xplane(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    ctx = Context(job, win, setup_s, reduced)
    for reader in (cell.per_layer if trace else cell.end_to_end):
        value = reader.read(ctx)
        if value is not None:
            metrics[reader.name] = {'value': float(value),
                                    'unit': reader.unit}

    traffic = cell.traffic
    records = job.records(traffic['check_models'])
    problems = job.problems
    loss = job.estimator().get('loss', 'hinge')
    job.release()
    del job, ctx
    t_ref = time.perf_counter()
    numbers, each = check.measure(records, problems,
                                  traffic['check_planes'], seed, loss)
    correct, checks = check.verdict(numbers, cell.limits)
    failed = check.failures(each, cell.limits)
    log(f'[reference] {time.perf_counter() - t_ref:.3f} s over '
        f'{len(records)} models')
    for name in sorted(set(numbers) - set(checks)):
        log(f'{name} {float(numbers[name])!r} (no limit in this cell)')

    dev = devices[0]
    result = {
        'correct': correct,
        'checked': len(records),
        'attempted': int(win.counts[traffic['attempted']]),
        'failed': failed,
        'metrics': metrics,
        'device': {'platform': dev.platform, 'kind': dev.device_kind,
                   'count': len(devices), 'memory_peak_bytes': mem},
        'window_compiles': window_compiles,
        'seed': seed,
        'numbers': {k: float(v) for k, v in numbers.items()},
    }
    if reduced is not None:
        result['device']['busy_s'] = reduced['busy_s']
        result['device']['window_s'] = reduced['window_s']
        result['breakdown'] = reduced['breakdown']
    result['checks'] = checks
    return result


def _finite(x):
    """x, or its name where JSON has no number for it (inf, nan)."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, float) and not math.isfinite(x):
        return repr(x)
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload, ROOT)
    import repro  # noqa: F401  (fails here when the program is absent)
    import jax
    import device
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoAccelerator as e:
        log(f'run.py: {e}')
        return 1
    cache = device.setup_compile_cache(ROOT)
    log(f'[device] {devices[0].device_kind} x{len(devices)}, '
        f'compile cache {cache}')
    with jax.default_device(devices[0]):
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices)
    for name, c in result['checks'].items():
        log(f"{name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(_finite(result), allow_nan=False), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
