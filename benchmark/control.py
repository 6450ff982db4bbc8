"""Read the control's numbers at a cell's own size.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

The control is the plain reference one precision step down, put in the
program's place (``yardstick.control``): ordered reads sorted on a 32-bit
key, combined reads summed in int16, plain reads carrying each record's
words in 16 bits (the key's high word dropped, its low word and every
value lane cut to 16 bits). For each seed it makes the cell's
map outputs, compares the control's partitions with the reference as a
run compares the program's (row counts of all partitions, rows of the
sampled eighth), and over every partition, and prints one JSON line of
the numbers. It has to exceed a limit on every seed for the limits to
separate a sound run from this one; the exit code says whether it did.
The benchmark's own runs do not run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import yardstick  # noqa: E402


def control_numbers(config, chips: int, seed: int) -> dict:
    """The control's numbers over every partition, and over the partitions
    a run compares rows of in its first shuffle."""
    inputs = yardstick.make_inputs(config, chips, seed)
    expected = yardstick.reference(inputs, config)
    parts = yardstick.split_rows(yardstick.control(inputs, config))
    sizes = {r: k.shape[0] for r, (k, _) in parts.items()}
    sample = yardstick.sample_partitions(seed, 0, inputs.num_partitions)
    return {"all": yardstick.compare(expected, sizes, parts, config),
            "sampled": yardstick.compare(
                expected, sizes, {r: parts[r] for r in sample}, config)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        cell = {c["name"]: c for c in json.load(f)["workloads"]}[
            args.workload]
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        got = control_numbers(config, int(cell["chips"]), seed)
        fails = any(got["sampled"][k] > yardstick.LIMITS[k]
                    for k in yardstick.LIMITS)
        failed_all &= fails
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": got, "fails_a_limit": fails}),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
