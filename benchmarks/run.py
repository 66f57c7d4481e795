"""Benchmark driver — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only quantize,prune,...]
                                            [--artifact DIR]

Prints ``name,us_per_call,derived`` CSV. ``--artifact DIR`` additionally
writes one ``BENCH_<module>.json`` per module — the machine-readable
perf-trajectory record CI uploads (rows + host/backend metadata), so
regressions in e.g. the C-step dispatch columns are diffable across
commits.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time


MODULES = ["quantize", "prune", "lowrank", "showcase", "cstep", "serve",
           "roofline", "perf_variants", "matrix"]


def _write_artifact(directory: str, name: str, rows: list,
                    elapsed_s: float) -> None:
    import jax

    payload = {
        "bench": name,
        "jax_backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "platform": platform.platform(),
        "elapsed_s": round(elapsed_s, 3),
        "rows": rows,
    }
    path = os.path.join(directory, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(MODULES))
    ap.add_argument("--artifact", default=None, metavar="DIR",
                    help="also write BENCH_<module>.json per module")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else MODULES

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        mod = __import__(f"benchmarks.bench_{name}",
                         fromlist=["run"])
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # keep the suite going
            print(f"bench_{name},0,ERROR {type(e).__name__}: {e}")
            failures += 1
            continue
        for r in rows:
            derived = str(r["derived"]).replace(",", ";")
            print(f"{r['name']},{r['us_per_call']:.1f},{derived}",
                  flush=True)
        elapsed = time.time() - t0
        print(f"# bench_{name} done in {elapsed:.1f}s", file=sys.stderr)
        if args.artifact:
            _write_artifact(args.artifact, name, rows, elapsed)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
