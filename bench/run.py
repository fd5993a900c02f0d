#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload city2k-daemon --seed 7 --seconds 30 --trace 0

Set-up (building the deployment from the configuration and ``--seed``,
training, warming every shape the cell uses, from the persistent compile
cache in ``<checkout>/.jax_cache``) counts as ``setup_s``; then the cell
measures for ``--seconds``.  ``--trace 1`` profiles the first seconds of
the window and reports the per-layer metrics; ``--trace 0`` reports the
end-to-end ones.  After the window the program's state is freed and what
the window produced is compared with the plain reference
(``bench/reference.py``); ``correct`` says whether every compared number
is within its limit (``bench/limits/<cell>.json``).  The last line of
standard output is one JSON object; the compared numbers and their
limits are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, the command
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # libtpu's logs stay inside the checkout
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench_out" / "tpu_logs"))
    Path(os.environ["TPU_LOG_DIR"]).mkdir(parents=True, exist_ok=True)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax

    import harness

    spec = harness.Spec(ROOT)
    cell = spec.cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind}). Not running.", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    out = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                      T_START, spec=spec)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
