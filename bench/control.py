"""The control of a cell's comparison: the configuration's reference with
its int8 weights cut to int4 (the next precision below the
configuration's), put in the program's place, on the frames and rows a
run of that cell with the same seed checks.  The comparison has to
reject it:

    python bench/control.py --workload mnist-bulk --seeds 11,12,13

One line per seed with the numbers the run compares (``rows_wrong``,
``preds_wrong``) as the control reads them.
"""

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def control_readings(name: str, seed: int, seconds: float, bits: int = 4) -> dict:
    import jax
    import numpy as np

    import harness
    import load
    from cell import compare

    spec = harness.load_spec()
    wl = harness.find_workload(spec, name)
    cfg = harness.load_config(spec, wl["config"])
    family = harness.load_family(spec, wl["config"])
    traffic = harness.load_traffic(wl["traffic"])
    rng = np.random.default_rng(seed)
    model = jax.tree.map(np.asarray, family.make_model(jax, cfg, seed))
    if traffic["entry"] == "service":
        pool = family.make_frames(rng, traffic["pool_frames"], cfg)
        _, start, sampled = load.plan_open(rng, traffic, seconds, len(pool))
        k = traffic["frames_per_request"]
        keyed = [(int(s), pool[s:s + k]) for s in start[sampled]]
    else:
        k = traffic["frames_per_request"]
        batches = [family.make_frames(rng, k, cfg) for _ in range(traffic["pool_requests"])]
        rows = load.plan_closed(rng, traffic, batches)
        keyed = [(b, batches[b][r]) for b, r in enumerate(rows)]
    checked = []
    for key, frames in keyed:
        sums, preds, _ = family.reference(frames, cfg, model, weight_bits=bits)
        checked.append((key, frames, sums, preds))
    out = compare(checked, cfg, family, model)
    return {"workload": name, "seed": seed, "weight_bits": bits, **out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=None,
                    help="window the plan is drawn for (default: run_seconds)")
    args = ap.parse_args(argv)
    import harness

    seconds = args.seconds or harness.load_spec()["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_readings(args.workload, seed, seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT / "src"))
    sys.exit(main())
