"""Run one benchmark cell and print its result as the last line of stdout.

    python3 bench/run.py --workload gli85.path --seed 7 --seconds 51 --trace 0
    JAX_PLATFORMS=cpu python3 bench/run.py --workload gli85.path --seed 7 \
        --seconds 2 --trace 0 --rehearse

`--trace 0` prints the cell's end-to-end metrics, `--trace 1` its per-layer
metrics, read from a profiler trace of the window's first jobs. Without an
accelerator, or with fewer chips than the cell asks for, it exits non-zero
and prints no result. `--rehearse` runs the configuration's tiny rehearsal
shape on whatever JAX finds (kernels in interpret mode on the CPU) and
never prints a result line: its numbers measure nothing.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import repro  # noqa: F401
    except ImportError:
        return _fail("the program (src/repro) is not in this checkout")
    import jax

    from bench import harness

    if not args.rehearse:
        harness.log(f"compile cache: {harness.enable_compile_cache(jax)}", T0)
    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), t0=T0,
            require_chip=not args.rehearse, rehearse=args.rehearse)
    except harness.NoChip as e:
        return _fail(f"{e}; the benchmark does not fall back to the CPU")
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    if args.rehearse:
        result.pop("correct")
        harness.log(f"rehearsal: {json.dumps(result)}", T0)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
