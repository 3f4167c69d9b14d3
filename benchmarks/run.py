"""Benchmark aggregator — one section per paper table + kernel + roofline.

Prints ``name,us_per_call,derived`` CSV lines (benchmarks.common.emit).
Fast by default (~5-10 min on CPU); per-table modules support --full runs.
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (bipolar_vs_split, kernel_bench, roofline,
                            table1_multiplier_mse, table2_adder_mse,
                            table3_accuracy, table3_energy)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    sections = [
        ("table1", table1_multiplier_mse.run),
        ("table2", table2_adder_mse.run),
        ("table3_energy", table3_energy.run),
        ("kernel", kernel_bench.run),
        ("bipolar", bipolar_vs_split.run),
        ("table3_accuracy", table3_accuracy.run),
        ("roofline_single", lambda: roofline.run("single")),
        ("roofline_multi", lambda: roofline.run("multi")),
    ]
    failed = []
    for name, fn in sections:
        try:
            fn()
        except Exception:  # noqa: BLE001 — keep the suite running
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED_SECTIONS,{len(failed)},{';'.join(failed)}")
        sys.exit(1)
    print("all_sections,0,ok")


if __name__ == "__main__":
    main()
