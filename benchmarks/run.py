# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (us_per_call doubles as the metric value for non-timing rows).
# A phase that raises prints a ``*_FAILED`` row, the others still run, and
# the process exits 1.
import sys
import time


def main() -> int:
    t0 = time.time()
    from benchmarks import (fleet_bench, optimizer_scale, roofline_table,
                            sim_bench)
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    all_rows, failed = [], []
    for mod in (fleet_bench, optimizer_scale, roofline_table, sim_bench):
        try:
            all_rows += mod.run()
        except Exception as e:  # noqa: BLE001
            failed.append(mod.__name__)
            all_rows.append((f"{mod.__name__}_FAILED", -1.0,
                             f"{type(e).__name__}: {e}"))
    for name, val, derived in all_rows:
        d = str(derived).replace(",", ";")
        print(f"{name},{float(val):.4f},{d}")
    print(f"total_wall_s,{time.time() - t0:.1f},benchmark harness runtime")
    if failed:
        print(f"FAIL: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
