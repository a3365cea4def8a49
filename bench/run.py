"""Benchmark entry point: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. `BENCHMARK.json` there names the cells;
`bench/harness.py` finds each cell's deployment, traffic mix, driver and
per-layer metric readers by name. With `--trace 0` the last line of
stdout is the result with the cell's end-to-end metrics; with
`--trace 1` the window runs under the profiler and the line carries the
per-layer metrics, the device's busy time and a breakdown. The numbers
compared for `correct` come last, in the line and on stderr.

Exits 2, printing no result, where JAX's first device is not a TPU or
there are fewer chips than the cell asks for. JAX's persistent compile
cache is `$JAX_COMPILATION_CACHE_DIR` when set, else `.jax_cache/` in
the checkout.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"bench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program is not in this checkout ({ROOT / 'src'})",
              file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU; JAX's first device is {devices[0].platform!r}"
              f" ({devices[0].device_kind})", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {cell['name']} needs {cell['chips']} chips, JAX "
              f"sees {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache

    import harness
    from seams import CompileMeter

    print(f"bench: compile cache {use_compile_cache(ROOT)}", file=sys.stderr)
    meter = CompileMeter()
    out = harness.run_cell(spec, args.workload, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           root=ROOT, t0=T0, platform="tpu", meter=meter)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
