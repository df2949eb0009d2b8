"""The control of ``correct`` for a language-model configuration, and faults planted in
the reference, read on the chip for several seeds in one process: ``tools/control.py``
for any configuration whose weights come from ``lm_weights`` and whose rows are tokens.
The reference is put in the program's place and computed in int8 (every projection, the
router, the experts and the head: input per tensor, matrix per output column), the
nearest precision below the configuration's bfloat16, over ``--rows`` rows of the cell's
traffic; each ``--fault`` is the reference with one of its own ``forward`` keywords set
(``passes=3``, ``norm_in_loop=false``, ``use_decay=false``, ``use_rope_key=false``). Every
output that the configuration's ``program`` block names is compared beside the head, by
``comparers/logprob_rows.compare_outputs``.

    python3 benchmarks/tools/control_lm.py --config benchmarks/configs/<c>.json \
        --traffic benchmarks/traffic/<t>.json --seeds 11,12,13 --rows 4 \
        [--fault passes=3 --fault norm_in_loop=false] [--rehearsal 1]

Prints one JSON line per seed and stand-in with the numbers a run compares, each beside
its limit, and ``correct`` as the comparer decides it. Exits 0 only if every stand-in
came out not correct on every seed. The benchmark's own runs never call this."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _fault(text: str) -> tuple[str, dict]:
    key, _, value = text.partition("=")
    try:
        parsed = json.loads(value)
    except ValueError:
        parsed = value
    return text, {key: parsed}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", required=True)
    parser.add_argument("--traffic", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--rows", type=int, default=4)
    parser.add_argument("--fault", action="append", default=[],
                        help="<reference keyword>=<JSON value>: one more stand-in")
    parser.add_argument("--rehearsal", type=int, default=0)
    args = parser.parse_args()
    from sparkdl_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    import numpy as np

    from benchmarks import devices, lm_weights, model
    from benchmarks.comparers.logprob_rows import compare_outputs, reference_of
    config, traffic = model.load_config(args.config), model.load_config(args.traffic)
    if args.rehearsal:
        traffic.update(traffic["rehearsal"])
        config.update(traffic["config"])
    else:
        devices.require_chips(1)
    head, others = config["head"], list(config["program"].get("outputs", {}))
    stand_ins = [("int8", {"quant": "int8"})] + [_fault(f) for f in args.fault]
    passed = {name: [] for name, _ in stand_ins}
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = lm_weights.make_weights(config, seed)
        tokens = lm_weights.token_rows(seed, args.rows, traffic["row_tokens"],
                                       config["vocab_size"], traffic["zipf_exponent"])
        reference = reference_of(config, weights, tokens)
        for name, keywords in stand_ins:
            answers = reference_of(config, weights, tokens, **keywords)
            for key in others:  # a loop a pass short: the passes it has, the last none
                short = reference[key].shape[1] - answers[key].shape[1]
                if short > 0:
                    answers[key] = np.pad(answers[key], ((0, 0), (0, short)))
            ok, compared = compare_outputs(answers, reference, head, config["correct"])
            passed[name].append(ok)
            print(json.dumps({"config": config["name"], "seed": seed, "stand_in": name,
                              "correct": ok, "compared": compared}), flush=True)
    for name, oks in passed.items():
        print(f"{name}: not correct on {oks.count(False)} of {len(oks)} seeds", flush=True)
    return 1 if any(ok for oks in passed.values() for ok in oks) else 0


if __name__ == "__main__":
    sys.exit(main())
