"""Benchmark harness: one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (see benchmarks/common.py).
Run:  PYTHONPATH=src python -m benchmarks.run [--only fig2,table2]
"""
import argparse
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache

from .common import emit_header

BENCHES = [
    ("table2", "benchmarks.bench_table2_models"),
    ("fig2", "benchmarks.bench_fig2_dispatch"),
    ("fig6", "benchmarks.bench_fig6_scalability"),
    ("fig7", "benchmarks.bench_fig7_systems"),
    ("table3", "benchmarks.bench_table3_layers"),
    ("fig8", "benchmarks.bench_fig8_coldstart"),
    ("scheduler", "benchmarks.bench_scheduler"),
    ("paged", "benchmarks.bench_paged"),
    ("prefill", "benchmarks.bench_prefill"),
    ("spec", "benchmarks.bench_spec"),
    ("prefix", "benchmarks.bench_prefix"),
    ("tp", "benchmarks.bench_tp"),
    ("kvquant", "benchmarks.bench_kvquant"),
    ("faults", "benchmarks.bench_faults"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated bench keys")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    enable_compile_cache()
    emit_header()
    failures = []
    for key, module in BENCHES:
        if only and key not in only:
            continue
        try:
            mod = __import__(module, fromlist=["run"])
            mod.run()
        except Exception:
            failures.append(key)
            traceback.print_exc()
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
