"""Run one cell as ``bench/run.py`` does, and print what the program
records of its own over the measured window:

    python bench/probe.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Before the result line it prints ``stages {...}``: count and p50/p95/p99
(us) of each stage histogram of the engine (``dispatch``, ``wait``,
``fetch``) and of the service (``queue``, ``slot``, ``dispatch``,
``complete``, ``resolve``, ``latency``), windowed by two snapshots
(``bench/stages.py``).  With ``--trace 1`` it also prints ``idle by span
{...}``: the first device's idle seconds per ``serve.*`` span and the
longest gaps so labelled (``bench/spans.py``).  The cell, its window,
its comparison with the reference and its result line are
``cell.run_cell``'s own; the probe only wraps the window, the family's
``build_engine`` and the service's construction, to read them.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))


def _snapshot(found: dict) -> dict:
    import stages

    engine, service = found.get("engine"), found.get("service")
    if engine is None:
        return {}
    arch = engine.models()[0]
    out = {"engine": stages.snapshot(engine.stats(arch), stages.ENGINE_STAGES)}
    if service is not None:
        out["service"] = stages.snapshot(service.stats(arch), stages.SERVICE_STAGES)
    return out


def probe_cell(name, seed, seconds, trace, devices, t_start, **kw):
    """``cell.run_cell`` with the probe's lines printed at the window's end."""
    import cell
    import harness
    import spans
    import stages
    from harness import say

    import repro.serve as serve

    found = {}
    load_family = harness.load_family

    def family_of(*a, **k):
        family = load_family(*a, **k)
        build_engine = family.build_engine

        def build(*a, **k):
            found["engine"], arch = build_engine(*a, **k)
            return found["engine"], arch

        family.build_engine = build
        return family

    class Service(serve.ServingService):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            found["service"] = self

    class Window(cell._Window):
        def open(self):
            self.before = _snapshot(found)
            super().open()

        def close(self):
            super().close()
            after = _snapshot(found)
            say("stages " + json.dumps({
                part: stages.summary(stages.window(self.before.get(part, {}), snap))
                for part, snap in after.items()}))
            if self.trace_dir is not None:
                say("idle by span " + json.dumps(spans.reduce_trace(str(self.trace_dir))))

    patches = [(harness, "load_family", family_of), (serve, "ServingService", Service),
               (cell, "_Window", Window)]
    saved = [(mod, attr, getattr(mod, attr)) for mod, attr, _ in patches]
    for mod, attr, new in patches:
        setattr(mod, attr, new)
    try:
        return cell.run_cell(name, seed, seconds, trace, devices, t_start, **kw)
    finally:
        for mod, attr, old in saved:
            setattr(mod, attr, old)


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
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"probe: {args.workload} needs {chips} TPU chips, JAX found "
              f"{len(devices)} {devices[0].platform}; nothing run", file=sys.stderr)
        return 2
    probe_cell(args.workload, args.seed, args.seconds, bool(args.trace),
               devices[:chips], T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
