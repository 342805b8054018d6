"""Roofline terms per (arch x shape) from the dry-run artifacts
(EXPERIMENTS.md §Roofline reads the same data)."""
import glob
import os

from .common import Timer, emit


def run():
    from repro.launch import roofline

    d = os.environ.get("DRYRUN_DIR", "results/dryrun")
    if not glob.glob(os.path.join(d, "*.json")):
        print("# no dry-run artifacts found — run repro.launch.dryrun first")
        emit("roofline", 0, "skipped")
        return
    with Timer() as t:
        recs = roofline.load(d, multi_pod=False)
        for r in recs:
            if "skipped" in r:
                print(f"# {r['arch']:>20s} {r['shape']:<12s} SKIPPED")
                continue
            print(f"# {r['arch']:>20s} {r['shape']:<12s} "
                  f"comp={r['t_comp_s']*1e3:8.2f}ms mem={r['t_mem_s']*1e3:8.2f}ms "
                  f"coll={r['t_coll_s']*1e3:7.2f}ms -> {r['dominant']:<10s} "
                  f"frac={r['roofline_fraction']:.3f}")
    emit("roofline_terms", t.us, f"cells={len(recs)}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
