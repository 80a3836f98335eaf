"""Benchmark harness — one entry per paper table/figure + the roofline table.

    PYTHONPATH=src python -m benchmarks.run [names...]

Prints ``name,us_per_call,derived`` CSV rows.  These are CPU readings of
the paper's tables; the chip benchmark is ``bench/`` (``BENCHMARK.json``).

Environment: REPRO_BENCH_SCALE=ci|mid|paper controls problem sizes (ci
default); REPRO_BENCH_SMOKE=1 shrinks the roofline bench to seconds-scale.
"""
import sys

import jax

jax.config.update("jax_enable_x64", True)

from . import (bench_fig4_smoothness, bench_fig10_pinrmse, bench_fig11_nrmse,
               bench_roofline, bench_table1_vec, bench_table4_holdout)

BENCHES = {
    "fig4": bench_fig4_smoothness.run,
    "table1": bench_table1_vec.run,
    "table4": bench_table4_holdout.run,
    "fig10": bench_fig10_pinrmse.run,
    "fig11": bench_fig11_nrmse.run,
    "roofline": bench_roofline.run,
}

def main() -> None:
    names = sys.argv[1:] or list(BENCHES)
    print("name,us_per_call,derived")
    for name in names:
        BENCHES[name]()


if __name__ == "__main__":
    main()
