"""Benchmark harness — one entry per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (plus commented detail lines)."""
import sys
import traceback


def main() -> None:
    from . import (
        bench_ablation,
        bench_compare,
        bench_roofline,
        bench_serving,
        bench_table1,
        bench_validation,
    )

    benches = [
        ("table1 (OS/WS EDP ratios)", bench_table1.run),
        ("tableV (engine validation)", bench_validation.run),
        ("fig7 (compass vs baselines)", bench_compare.run),
        ("fig9/10+tableVII (serving strategies)", bench_serving.run),
        ("fig11 (ablation)", bench_ablation.run),
        ("roofline (dry-run terms)", bench_roofline.run),
    ]
    failures = []
    print("name,us_per_call,derived")
    for name, fn in benches:
        print(f"# === {name} ===")
        try:
            fn()
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
