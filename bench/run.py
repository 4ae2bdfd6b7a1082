"""The benchmark command: one run of one cell on the chip.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  In order: build the cell's
configuration from the seed, stage its planes, warm up every shape the
window uses (all counted in ``setup_s``), measure for ``--seconds``, check
a seeded sample of what the window produced against the plain reference,
and print one JSON line last on stdout.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces the window and reports its
per-layer metrics, the device's busy time and a breakdown.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.  JAX's compile cache is ``$JAX_COMPILATION_CACHE_DIR``
when set, else ``<checkout>/.jax_cache``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NO_CHIP = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness, spec
    chips = spec.Benchmark(ROOT).workload(args.workload)["chips"]

    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devs = jax.devices()
    except RuntimeError as exc:
        print(f"bench.run: JAX found no accelerator: {exc}", file=sys.stderr)
        return NO_CHIP
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench.run: needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return NO_CHIP
    import repro  # noqa: F401  (the system under test must be importable)

    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), mode="auto", t_start=T_START)
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    # The result is out; skip interpreter teardown, where the runtime's
    # threads have crashed a finished process (SIGSEGV in PyObject_GC_Del).
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
