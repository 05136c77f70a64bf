"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(and ``breakdown`` with ``--trace 1``), then ``checks``, each number
compared beside its limit. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled window.

Exits non-zero, printing no result, when JAX's first device is not a
TPU, when it finds fewer chips than the cell asks for, or when the
device kind is not in ``chipbench/peaks.json``.

Two options serve the one who sets the benchmark up; its own runs use
neither. ``--control`` judges the entry's control (the reference in
the precision below the configuration's) in the program's place: a
sound benchmark reads ``correct`` false with it on every seed.
``--set KEY=VALUE`` replaces one number of the traffic file, as a
sweep for the knee of an open-loop cell does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE")
    args = ap.parse_args(argv)
    override = {}
    for item in args.set:
        key, _, value = item.partition("=")
        override[key] = float(value)

    sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
    from harness import NoChip, execute
    try:
        out = execute(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START,
                      traffic_override=override, control=args.control)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
