#!/usr/bin/env python3
"""Readings that the limits of ``run.py``'s checks are set from.

    python chipbench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 --seconds <s>

In one process on the chip (the cell's programs then compile once), runs the
cell as ``run.py`` does, less the warm-up, on every ``--seeds`` seed: the
sound readings, whose largest is each check's lower reading. Then, for each of the configuration's
``controls``, on every ``--control-seeds`` seed it runs the cell with the
control's ``override`` given to the service, which breaks one guarantee that
the configuration states, while the check holds the run to the
configuration as stated: the control readings, whose smallest is the upper
reading. Prints one JSON line per run, and last the lower and upper reading
of every check. ``run.py``'s own runs never run a control.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    bench = run.Benchmark(run.ROOT)
    cell = bench.cell(args.workload)
    run.use_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        run.log("control.py: needs the cell's TPU chips; nothing run")
        return 2
    groups = [("sound", args.seeds, None)]
    groups += [(c["name"], args.control_seeds, c["override"]) for c in cell.config["controls"]]
    readings = {}
    for kind, group, override in groups:
        for seed in group:
            run.T_START = run.time.perf_counter()
            line = run.run_cell(bench, args.workload, seed, args.seconds, False,
                                service_override=override, warm=False)
            print(json.dumps({"kind": kind, "seed": seed, "correct": line["correct"],
                              "attempted": line["attempted"], "checks": line["checks"],
                              "metrics": line["metrics"]}),
                  flush=True)
            for k, c in line["checks"].items():
                readings.setdefault(kind, {}).setdefault(k, []).append(c["value"])
    summary = {
        k: {"lower": max(v), "sound": v,
            **{kind: {"upper": min(r.get(k, [float("nan")])), "readings": r.get(k, [])}
               for kind, r in readings.items() if kind != "sound"}}
        for k, v in readings["sound"].items()
    }
    print(json.dumps({"workload": args.workload, "readings": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
