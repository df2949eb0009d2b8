"""PR 34's check of what cell 6's comparison refuses: the reference itself put in the
program's place at the chip's own size (rows of 4,096 tokens, the configuration's file as it
is) and held to the configuration's limits by its own comparer, as
`benchmarks/tools/control_lm.py` holds the int8 control of cells 4 and 5 (that tool asks the
reference for a `routing` output, which a dense model has not). Three stand-ins: the int8
control (every projection, the SwiGLU and the head: input per tensor, matrix per output
column), the loop one pass short (`passes=3`), and the final norm left out of the loop
(`norm_in_loop=False`: the next pass starts from the layers' output, not from its norm). One
JSON line a seed and stand-in, then how many came out not correct. With PROGRAM=1 the program
itself (`drivers/token_stream_looped.model_function`) is read on the same rows first.

    chiprun -- python3 tools/chip_calls/pr34_faults.py --seeds 11,12 [--rows 2] [--only int8] [--rehearsal 1]
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from sparkdl_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
import numpy as np  # noqa: E402

from benchmarks import lm_weights, model  # noqa: E402
from benchmarks.comparers import logprob_rows_looped as comparer  # noqa: E402

STAND_INS = {"int8": {"quant": "int8"}, "three_passes": {"passes": 3},
             "norm_outside_loop": {"norm_in_loop": False}}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--rows", type=int, default=2)
    parser.add_argument("--only", default=",".join(STAND_INS))
    parser.add_argument("--rehearsal", type=int, default=0)
    args = parser.parse_args()
    config = model.load_config(os.path.join(ROOT, "benchmarks/configs/ouro_2p6b.json"))
    traffic = model.load_config(os.path.join(ROOT, "benchmarks/traffic/tokens_stream_4k.json"))
    if args.rehearsal:
        traffic.update(traffic["rehearsal"])
        config.update(traffic["config"])
    names = args.only.split(",")
    passed = {name: [] for name in names}
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = lm_weights.make_weights(config, seed)
        tokens = lm_weights.token_rows(seed, args.rows, traffic["row_tokens"],
                                       config["vocab_size"], traffic["zipf_exponent"])
        sound, sound_pdf = comparer.reference_outputs(config, weights, tokens)
        if os.environ.get("PROGRAM"):
            from benchmarks.drivers.token_stream_looped import model_function
            mf = model_function(config, weights, traffic["row_tokens"])
            out = [mf({"tokens": tokens[lo:lo + 2]}) for lo in range(0, len(tokens), 2)]
            ok, compared = comparer.compare_rows(
                np.concatenate([np.asarray(o["logprobs"]) for o in out]),
                np.concatenate([np.asarray(o["exit_pdf"]) for o in out]),
                sound, sound_pdf, config["correct"])
            print(json.dumps({"seed": seed, "stand_in": "the program", "correct": ok,
                              "compared": compared}), flush=True)
            mf = out = None
        for name in names:
            answers, pdf = comparer.reference_outputs(config, weights, tokens, **STAND_INS[name])
            if pdf.shape != sound_pdf.shape:  # a pass short: the passes it has, the last none
                pdf = np.concatenate([pdf, np.zeros((len(pdf), sound_pdf.shape[1] - pdf.shape[1]))], axis=1)
            ok, compared = comparer.compare_rows(answers, pdf, sound, sound_pdf, config["correct"])
            passed[name].append(ok)
            print(json.dumps({"seed": seed, "stand_in": name, "correct": ok, "compared": compared}),
                  flush=True)
    for name, oks in passed.items():
        print(f"{name}: not correct on {oks.count(False)} of {len(oks)} seeds", flush=True)
    return 1 if any(ok for oks in passed.values() for ok in oks) else 0


if __name__ == "__main__":
    sys.exit(main())
