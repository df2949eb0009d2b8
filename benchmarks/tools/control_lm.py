"""The control of ``correct`` for a language-model configuration, read on the chip for
several seeds in one process: ``tools/control.py`` for a configuration whose weights
come from ``lm_weights`` and whose rows are tokens. The reference is put in the
program's place and computed in int8 (every projection, the router, the experts and the
head: input per tensor, matrix per output column), the nearest precision below the
configuration's bfloat16, over ``--rows`` rows of the cell's traffic.

    python3 benchmarks/tools/control_lm.py --config benchmarks/configs/<c>.json \
        --traffic benchmarks/traffic/<t>.json --seeds 11,12,13 --rows 4 [--rehearsal 1]

Prints one JSON line per seed with the numbers a run compares, each beside its limit,
and ``correct`` as the configuration's own comparer decides it. Exits 0 only if the
control came out not correct on every seed. The benchmark's own runs never call this."""

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
    parser.add_argument("--traffic", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--rows", type=int, default=4)
    parser.add_argument("--rehearsal", type=int, default=0)
    args = parser.parse_args()
    from sparkdl_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    from benchmarks import devices, lm_weights, model
    config, traffic = model.load_config(args.config), model.load_config(args.traffic)
    if args.rehearsal:
        traffic.update(traffic["rehearsal"])
        config.update(traffic["config"])
    else:
        devices.require_chips(1)
    comparer = importlib.import_module(f"benchmarks.comparers.{config['correct']['comparer']}")
    passed = []
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = lm_weights.make_weights(config, seed)
        tokens = lm_weights.token_rows(seed, args.rows, traffic["row_tokens"],
                                       config["vocab_size"], traffic["zipf_exponent"])
        reference = lm_weights.reference_outputs(config, weights, tokens)
        control = lm_weights.reference_outputs(config, weights, tokens, quant="int8")
        is_correct, compared = comparer.compare_rows(control, reference, config["correct"])
        passed.append(is_correct)
        print(json.dumps({"config": config["name"], "seed": seed, "control": "int8",
                          "correct": is_correct, "compared": compared}), flush=True)
    print(f"control not correct on {passed.count(False)} of {len(passed)} seeds", flush=True)
    return 1 if any(passed) else 0


if __name__ == "__main__":
    sys.exit(main())
