"""Run one cell of the benchmark on the machine's TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic come from ``BENCHMARK.json``
and the files it names.  Set-up (data and store from the seed, load,
device state, every compile, traffic warm-up) is timed as ``setup_s``;
then the traffic runs for ``--seconds`` and the answers are checked
against the plain reference.  ``--trace 1`` also records a profiler trace
of part of the window and reports the per-layer metrics instead of the
end-to-end ones.

The last lines on standard error are the numbers compared, each with its
limit; the last line on standard output is the result as one JSON object.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def configure_cache() -> str:
    """The program's persistent compilation cache: ``<checkout>/.jax_cache``,
    or ``JAX_COMPILATION_CACHE_DIR`` where that is set."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    # cache every program, also those that compile in under a second, so
    # that a run after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def print_check(check: dict) -> None:
    for name, c in check.items():
        lim = (f"<= {c['max']}" if "max" in c else f">= {c['min']}")
        print(f"[check] {name} = {c['value']} (limit {lim})",
              file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.spec import load_cell
    cell = load_cell(ROOT, args.workload)
    cache = configure_cache()
    import jax
    devs = jax.devices()
    print(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}; compile cache {cache}", file=sys.stderr,
          flush=True)
    if devs[0].platform != "tpu":
        print("no TPU found: the benchmark runs only on the chip",
              file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"cell {cell.name} needs {cell.chips} chips, found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    from bench.harness import run_cell
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print_check(out["check"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
