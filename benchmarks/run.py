"""Benchmark entry point — one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [section ...]``
Sections: table1 table4 figs serving server kernels roofline shard
granularity stream megakernel obs
(default: all).  Prints ``name,us_per_call,derived`` CSV.

``--smoke`` instead recomputes the schedule-deterministic counters (round
counts, exchange totals, donations) and exits non-zero if any disagrees
with the checked-in ``BENCH_*.json`` — the CI regression guard
(benchmarks/smoke.py).
"""
from __future__ import annotations

import sys


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    argv = sys.argv[1:]
    if "--smoke" in argv:
        extra = [a for a in argv if a != "--smoke"]
        if extra:
            sys.exit(f"--smoke runs alone (got extra args {extra}); run "
                     f"sections first, then the smoke check")
        from . import smoke

        sys.exit(1 if smoke.run() else 0)

    from . import (bench_figs, bench_granularity, bench_kernels,
                   bench_megakernel, bench_obs, bench_roofline,
                   bench_server, bench_serving, bench_shard, bench_stream,
                   bench_table1, bench_table4)

    sections = {
        "table1": bench_table1.run,
        "table4": bench_table4.run,
        "figs": bench_figs.run,
        "serving": bench_serving.run,
        "server": bench_server.run,
        "kernels": bench_kernels.run,
        "roofline": bench_roofline.run,
        "shard": bench_shard.run,
        "granularity": bench_granularity.run,
        "stream": bench_stream.run,
        "megakernel": bench_megakernel.run,
        "obs": bench_obs.run,
    }
    want = argv or list(sections)
    print("name,us_per_call,derived")
    for name in want:
        sections[name]()


if __name__ == "__main__":
    main()
