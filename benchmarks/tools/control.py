"""The control of ``correct``, read on the chip for several seeds in one process:
the reference put in the program's place and computed in int8, the nearest
precision below the configurations' bfloat16, over as many rows as a run compares.

    python3 benchmarks/tools/control.py --config benchmarks/configs/<c>.json \
        --seeds 11,12,13 --rows 288 [--rehearsal 1]

Prints one JSON line per seed with the numbers a run compares, each beside its
limit, and ``correct`` as the configuration's own comparer decides it. Exits 0 only
if the control came out not correct on every seed. The benchmark's own runs never
call this."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--rows", type=int, default=288)
    parser.add_argument("--rehearsal", type=int, default=0)
    args = parser.parse_args()
    from sparkdl_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    from benchmarks import devices, harness, model
    if not args.rehearsal:
        devices.require_chips(1)
    config = model.load_config(args.config)
    comparer = importlib.import_module(f"benchmarks.comparers.{config['correct']['comparer']}")
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = model.make_weights(config, seed)
        images = harness.image_rows(seed, args.rows, config["input_shape"])
        reference = model.reference_outputs(config, weights, images)
        control = model.reference_outputs(config, weights, images, quant="int8")
        is_correct, compared = comparer.compare_rows(control, reference, config["correct"])
        passed.append(is_correct)
        print(json.dumps({"config": config["name"], "seed": seed, "control": "int8",
                          "correct": is_correct, "compared": compared}), flush=True)
    print(f"control not correct on {passed.count(False)} of {len(passed)} seeds", flush=True)
    return 1 if any(passed) else 0


if __name__ == "__main__":
    sys.exit(main())
