"""The control at a cell's own size, without the engine: the plain
reference computed in the precision below the configuration's (float32 for
float64) is put in the program's place and judged by ``run.judge``, the
comparison every run ends with. Prints ``correct`` and every number
compared beside its limit; run by hand (PERF.md section 2 has the readings
the limits were set from). Needs no chip and imports no JAX.
``run.py --control float32`` does the same at the end of a whole run.

    python3 perfbench/tests/control_probe.py <cell> <seed> [<seed> ...]
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import datagen  # noqa: E402
import run      # noqa: E402


def control_reading(cell: dict, tables: dict, seed: int,
                    precision: str = "float32") -> dict:
    """name -> {"value", "limit"} of the control against the reference,
    for one seed."""
    config, check, query = cell["config"], cell["check"], cell["query"]
    columns = run.query_columns(cell, tables)
    ref = datagen.load_module("references", check["reference"])
    want, low = (datagen.reference_answer(config, tables, seed, columns,
                                          check["reference"], query, p)
                 for p in (config["precision"], precision))
    return run.judge(cell, [ref.answer_frame(query, low)], want)


def main(argv) -> int:
    cell = run.load_cell(argv[0])
    config = cell["config"]
    tables = config["tables"]
    for seed in map(int, argv[1:]):
        compared = control_reading(cell, tables, seed)
        correct = all(c["value"] <= c["limit"] for c in compared.values())
        print(json.dumps({"cell": cell["name"], "seed": seed,
                          "rows": tables[config["fact"]]["rows"],
                          "correct": correct, "compared": compared}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
