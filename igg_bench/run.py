"""The benchmark's one command.

    python3 igg_bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the chips of this machine and prints
one JSON line last on standard output; the numbers compared with the plain
reference, each with its limit, come last on standard error.  There is no
CPU fallback: a run that finds no TPU, or fewer chips than the cell asks
for, exits non-zero and prints no result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the persistent compile cache, at a fixed path inside the checkout, so a
#: cell's runs after its first read every program from it
CACHE_DIR = os.path.join(ROOT, ".igg_bench_cache", "jax")


def use_checkout_cache():
    """Keep JAX's persistent compile cache at `CACHE_DIR`.  The program's own
    default would follow JAX_COMPILATION_CACHE_DIR; the benchmark keeps its
    cache in the checkout whatever the machine sets."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)  # never evict a cell's programs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    sys.path.insert(0, ROOT)
    use_checkout_cache()
    import jax

    from igg_bench import harness

    marks = [("interpreter and jax import", harness.process_age_s(_T0))]
    try:
        import implicitglobalgrid_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"igg_bench: the program is not in this checkout: {e}", file=sys.stderr)
        return 2
    marks.append(("program import", harness.process_age_s(_T0)))
    devices = jax.devices()
    marks.append(("backend", harness.process_age_s(_T0)))
    if devices[0].platform != "tpu":
        print(f"igg_bench: no TPU: JAX's default platform is "
              f"{devices[0].platform!r} ({devices[0].device_kind})", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    log = lambda msg: print(f"igg_bench: {msg}", file=sys.stderr, flush=True)
    log("start: " + ", ".join(f"{n} at {t:.2f} s" for n, t in marks))
    try:
        line = harness.run(ROOT, spec, args.workload, args.seed, args.seconds,
                           bool(args.trace), devices, t0=_T0, log=log)
    except harness.BenchError as e:
        print(f"igg_bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"check correct {line['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
