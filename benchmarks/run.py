"""Benchmark runner: one module per paper figure/table (see DESIGN.md §8).

Prints ``name,us_per_call,derived`` CSV rows. Sizes are scaled for the 1-core
CPU container (constants documented per module); ledger-derived columns
(bytes/rounds) are scale-exact reproductions of the communication profile.
A module that fails prints a ``<module>_FAILED`` row, the remaining modules
still run, and the runner exits 1.

Run:  PYTHONPATH=src python -m benchmarks.run [module ...]
"""
from __future__ import annotations

import sys
import time
from typing import List, Optional

MODULES = [
    "bench_resizer_scaling",  # Fig 5a
    "bench_resizer_width",  # Fig 5b
    "bench_operator_resizer",  # Fig 6
    "bench_step_breakdown",  # Fig 7
    "bench_healthlnk",  # Fig 8
    "bench_placement",  # Fig 9
    "bench_crt_addition",  # Fig 10
    "bench_crt_distributions",  # Fig 11
    "bench_security_tradeoff",  # §5.4 example
    "bench_kernels",  # kernel layer
    "bench_service",  # SQL/service layer -> BENCH_service.json
    "bench_lm_roofline",  # LM dry-run roofline table
]


def main(argv: Optional[List[str]] = None) -> int:
    only = (sys.argv[1:] if argv is None else argv) or None
    print("name,us_per_call,derived")
    failed = []
    for mod_name in MODULES:
        if only and mod_name not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            rows = mod.run()
        except Exception as e:  # keep the suite going; surface the failure
            print(f"{mod_name}_FAILED,0.0,{type(e).__name__}:{e}")
            failed.append(mod_name)
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        print(f"# {mod_name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
