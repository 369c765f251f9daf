#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of the checkout. One process, the machine it is started on.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``) and ``device``;
with ``--trace 1`` also ``breakdown``. Progress goes to standard error.

It exits non-zero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for. Everything it writes stays inside the checkout: the
compile cache at ``.jax_cache/`` (or where ``JAX_COMPILATION_CACHE_DIR``
says), traces under ``benchmark/_out/``.
"""

import time

T_START = time.time()  # set-up is counted from here, imports included

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, "benchmark", "_out")
PLATFORM = "tpu"


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache's key), every entry kept; where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX uses that and nothing is set
    here."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(ROOT, ".jax_cache")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark.harness import cell as cell_mod
    from benchmark.harness import spec

    cell = spec.load_cell(args.workload)

    import jax

    devices = jax.devices()
    found = (f"platform {devices[0].platform!r}, kind "
             f"{devices[0].device_kind!r}, {len(devices)} device(s)")
    if devices[0].platform != PLATFORM or len(devices) < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} {PLATFORM} "
              f"chip(s); JAX found {found} - no result", file=sys.stderr)
        return 1
    print(f"[bench] {cell.name} on {found}", file=sys.stderr, flush=True)
    enable_compile_cache()
    result = cell_mod.run(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=T_START,
                          out_dir=OUT_DIR)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
