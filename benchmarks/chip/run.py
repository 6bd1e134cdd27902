"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the chips the cell asks for.
Refuses (exit 2, no result) where JAX finds no TPU or too few chips.  Makes
the weights on the device from ``--seed``, warms up every program the
cell's traffic dispatches, measures for ``--seconds``, checks a sample of
the served tokens against the plain reference, and prints one JSON object
as the last line of standard output: the cell's end-to-end metrics
(``--trace 0``) or its per-layer metrics from a profiler trace
(``--trace 1``).  Each number the check compared is printed beside its
limit, last on standard error and under ``checks`` in the result.
Compiled programs are kept where ``repro.launch.compile_cache`` puts them:
``$JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``.
A run whose timed path ran a kernel's XLA oracle in place of its Pallas
kernel (``repro.kernels.ops.FALLBACKS``) is refused (exit 3, no result).
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(CHECKOUT / "src"))


def info(tag: str, obj) -> None:
    print(f"{tag} {json.dumps(obj, default=str)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the float8 control and the int8 and bfloat16 witnesses (for setting limits)")
    ap.add_argument("--dump-trace", default=None,
                    help="write the traced window's events as gzipped JSON to this path")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    import harness
    import peaks

    cell = harness.Cell.load(args.workload, CHECKOUT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"refused: cell {cell.name} needs {cell.chips} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    peak = peaks.peaks(devices[0].device_kind)
    from repro.kernels import ops
    from repro.launch import compile_cache

    compile_cache.enable()

    result, checks, extra = harness.measure(
        cell, args.seed, args.seconds, bool(args.trace), t_start=T_START,
        control=bool(args.control), peak=peak, dump_trace=args.dump_trace)
    info("info", extra)
    if ops.FALLBACKS:
        print(f"refused: the timed path ran a kernel's fallback: {dict(ops.FALLBACKS)}",
              file=sys.stderr)
        return 3
    result["checks"] = {name: {"value": value, "limit": limit} for name, value, limit in checks}
    print(json.dumps(result), flush=True)
    for name, value, limit in checks:
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
