"""Benchmark driver — one module per paper table/figure + framework extras.
Prints ``name,us_per_call,derived`` CSV rows (derived is benchmark-specific:
speed factor, tasks/s, feature flag, ...)."""
from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import (fig7_scaling, ingest_bench, kernels_bench,
                            scenarios_bench, schedulers_bench, service_bench,
                            table2_features, throughput)
    suites = [
        ("table2_features", table2_features),   # paper Table II
        ("kernels", kernels_bench),
        ("schedulers", schedulers_bench),       # paper §IV use case
        ("scenarios", scenarios_bench),         # batched what-if fleet
        ("fig7_scaling", fig7_scaling),         # paper Fig. 7
        ("throughput", throughput),             # paper §IV/§VI claims
        ("ingest", ingest_bench),               # streaming vs legacy writer
        ("service", service_bench),             # what-if serving loop
    ]
    rows = []
    print("name,us_per_call,derived")
    for name, mod in suites:
        t0 = time.time()
        try:
            start = len(rows)
            mod.run(rows)
            for r in rows[start:]:
                print(f"{r[0]},{r[1]:.2f},{r[2]:.6g}", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"{name}_FAILED,0,0  # {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr,
              flush=True)


if __name__ == "__main__":
    main()
