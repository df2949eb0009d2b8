"""PR 32's check that cell 5's comparison covers the layer the cell is named for: the
reference itself with a fault planted in latent attention, put in the program's place at
the chip's own size (rows of 8,192 tokens, the configuration's file as it is) and held to
the configuration's limits by its own comparer, as `benchmarks/tools/control_lm.py` holds
the int8 control. The faults: the shared rotary key's part of the scores left out
(`use_rope_key=False`), the scores without `mscale^2` (both `mscale` keys 0), and rotary
frequencies without YaRN's scaling (`beta_fast` and `beta_slow` so small that no frequency
is divided by the factor). One JSON line a seed and fault, then how many passed the limits.

    chiprun -- python3 tools/chip_calls/pr32_mla_faults.py --seeds 11,12 [--rows 2] [--rehearsal 1]
"""
import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from sparkdl_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import lm_weights, model  # noqa: E402
from benchmarks.comparers import logprob_rows  # noqa: E402
from benchmarks.reference import axk1 as reference  # noqa: E402
from benchmarks.reference.nn import Net  # noqa: E402


def with_scaling(config, **keys):
    changed = copy.deepcopy(config)
    changed["rope_scaling"].update(keys)
    return changed


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--rows", type=int, default=2)
    parser.add_argument("--rehearsal", type=int, default=0)
    args = parser.parse_args()
    config = model.load_config(os.path.join(ROOT, "benchmarks/configs/axk1_ep16.json"))
    traffic = model.load_config(os.path.join(ROOT, "benchmarks/traffic/tokens_stream_p16.json"))
    if args.rehearsal:
        traffic.update(traffic["rehearsal"])
        config.update(traffic["config"])
    no_rope_key = jax.jit(lambda w, t: reference.forward(
        Net(params=w), t, config, use_rope_key=False)["logprobs"])
    faults = {
        "no_rope_key": lambda w, t: np.concatenate(
            [np.asarray(no_rope_key(w, t[lo:lo + 2])) for lo in range(0, len(t), 2)]),
        "no_mscale": lambda w, t: lm_weights.reference_outputs(
            with_scaling(config, mscale=0.0, mscale_all_dim=0.0), w, t),
        "no_yarn": lambda w, t: lm_weights.reference_outputs(
            with_scaling(config, beta_fast=0.05, beta_slow=0.05), w, t),
    }
    plain = reference.yarn_inv_freq(with_scaling(config, beta_fast=0.05, beta_slow=0.05))
    dim = config["qk_rope_head_dim"]
    assert np.allclose(plain, config["rope_theta"] ** (-np.arange(0, dim, 2) / dim))
    passed = {name: [] for name in faults}
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = lm_weights.make_weights(config, seed)
        tokens = lm_weights.token_rows(seed, args.rows, traffic["row_tokens"],
                                       config["vocab_size"], traffic["zipf_exponent"])
        sound = lm_weights.reference_outputs(config, weights, tokens)
        for name, fault in faults.items():
            ok, compared = logprob_rows.compare_rows(fault(weights, tokens), sound, config["correct"])
            passed[name].append(ok)
            print(json.dumps({"seed": seed, "fault": name, "correct": ok, "compared": compared}),
                  flush=True)
    for name, oks in passed.items():
        print(f"{name}: not correct on {oks.count(False)} of {len(oks)} seeds", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
