"""Run one cell of the benchmark once, on the chips of this machine:

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (configuration, traffic, chips) is the ``workloads`` entry of
``BENCHMARK.json`` named ``<name>``.  With ``--trace 0`` the result line
holds the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window.  The run exits
non-zero and prints no result when JAX finds no TPU, or fewer chips
than the cell asks for.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    chips = harness.find_workload(harness.load_spec(), args.workload)["chips"]
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX found "
              f"{len(devices)}; nothing run", file=sys.stderr)
        return 2
    from cell import run_cell

    run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
             devices[:chips], T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
