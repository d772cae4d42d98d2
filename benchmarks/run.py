"""Benchmark driver: one function per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV lines per the harness contract, and
human-readable tables above them.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import (ablation_thresholds, fig5_training_schemes,
                            fig678_latency_pdf, roofline_report,
                            table2_single_edge, table3_homogeneous,
                            table4_heterogeneous)

    csv_lines = ["name,us_per_call,derived"]

    def bench(name, module):
        t0 = time.perf_counter()
        _, derived = module.run(verbose=True)
        us = (time.perf_counter() - t0) * 1e6
        d = ";".join(f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                     for k, v in derived.items())
        csv_lines.append(f"{name},{us:.0f},{d}")

    bench("table2_single_edge", table2_single_edge)
    bench("table3_homogeneous", table3_homogeneous)
    bench("table4_heterogeneous", table4_heterogeneous)
    bench("fig5_training_schemes", fig5_training_schemes)
    bench("fig678_latency_pdf", fig678_latency_pdf)
    bench("ablation_thresholds", ablation_thresholds)

    bench("roofline_report", roofline_report)

    print("\n" + "\n".join(csv_lines))


if __name__ == "__main__":
    main()
