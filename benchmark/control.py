"""Readings of a cell's compared numbers over many seeds, in one process:

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 --seconds <s> [--precision high]

Each seed is drawn, loaded, driven through one window of the cell's own
timed path and compared, exactly as `harness.run` does; the programs
compile once. Without ``--precision`` the program runs as its
configuration states (the readings that set a limit's lower end). With
``--precision high`` it runs its own lower-precision path, the control,
which a limit has to fail. Prints one JSON line per seed. Needs the
chips the cell asks for, like the harness."""

import argparse
import json
import os
import sys
import time


def main(argv=None):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark import check, drive, harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--precision", choices=("high",))
    args = ap.parse_args(argv)
    res = harness.resolve(harness.load_spec(), args.workload)
    harness.device_stamp(res["cell"]["chips"])
    config = dict(res["config"])
    if args.precision:
        config["precision"] = args.precision
    harness.configure(config)
    harness.use_cache()
    op = drive.OPERATIONS[res["traffic"]["operation"]](
        config, res["cell"]["chips"], res["traffic"])
    tracer = drive.Tracer(None, op.devices)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        op.load(seed)
        op.build()
        if i == 0:
            op.warm()
        if isinstance(op, drive.Forward):
            # a whole cover, so every drawn subgrid is compared
            n_groups = -(-len(op.col_offs) // op.G)
            op.window(args.seconds, tracer, n_groups)
        else:
            op.window(args.seconds, tracer)
        op.free()
        verdict = check.compare(op, res["limits"])
        print(json.dumps({
            "seed": seed, "precision": config["precision"],
            "correct": verdict["correct"],
            "checks": {k: v["value"] for k, v in verdict["checks"].items()},
            "seconds": time.perf_counter() - t0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
