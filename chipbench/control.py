"""The control of a cell's ``correct``, at the cell's own size:

    python -m chipbench.control --workload <name> --seeds 1,2,3

For each seed: the data as a run makes it, the reference in float64, and
the reference computed in float32 (the nearest precision below what the
configurations state) put in the program's place. Prints each number
compared beside its limit; every seed has to come out NOT correct. numpy
only: it needs no chip and is no part of a benchmark run."""

from __future__ import annotations

import argparse
import json
import sys

from chipbench import cells, compare

OPERATIONS = 10  # as many operations of the window as the control stands in for


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    args = parser.parse_args(argv)
    cell = cells.load_cell(args.workload)
    config, suite = cell["config"], cell["suite"]
    driver = cells.plugin("drivers", cell["traffic"]["driver"])
    generator = cells.plugin("generators", config["generator"])
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        data = generator.generate(config["rows"], seed,
                                  config["generator_params"])
        records = [{"k": k, "rows": driver.rows_per_operation(config)}
                   for k in range(OPERATIONS)]
        verdict = compare.control_verdict(driver.slices, config, suite, data,
                                          records)
        passed += verdict["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": verdict["correct"],
                          "checks": verdict["checks"]}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
