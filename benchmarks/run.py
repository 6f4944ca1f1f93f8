# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper figure/table (DESIGN.md §6).

``python -m benchmarks.run``             — run everything
``python -m benchmarks.run fig16 fig18`` — run a subset by prefix
``python -m benchmarks.run --list``      — list registered benchmarks

Benchmark modules import JAX (and build models) at import time, so the
registry maps names to MODULE PATHS and imports lazily: ``--list`` and
prefix filtering resolve without importing anything heavy — the CI
smoke job uses this to sanity-check the registry in milliseconds.
"""
import importlib
import sys
import traceback

ALL = [
    ("fig02", "benchmarks.fig02_phase_characteristics"),
    ("fig03", "benchmarks.fig03_interference_pp"),
    ("fig04", "benchmarks.fig04_interference_pd"),
    ("fig05", "benchmarks.fig05_interference_dd"),
    ("fig11_15", "benchmarks.fig11_15_end_to_end"),
    ("fig16", "benchmarks.fig16_prefill_sched"),
    ("fig17", "benchmarks.fig17_predictor_overhead"),
    ("fig18", "benchmarks.fig18_decode_sched"),
    ("fig19", "benchmarks.fig19_load_balance"),
    ("predictor_accuracy", "benchmarks.predictor_accuracy"),
    ("flip_latency", "benchmarks.flip_latency"),
    ("roofline", "benchmarks.roofline_report"),
    ("paged_serving", "benchmarks.paged_serving"),
    ("fleet", "benchmarks.fleet"),
    ("wallclock", "benchmarks.wallclock"),
]


def main() -> None:
    wanted = sys.argv[1:]
    if "--list" in wanted:
        for name, _ in ALL:
            print(name)
        return
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = []
    for name, module in ALL:
        if wanted and not any(name.startswith(w) for w in wanted):
            continue
        try:
            importlib.import_module(module).run()
        except Exception as e:  # keep the harness running
            failures.append(name)
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
