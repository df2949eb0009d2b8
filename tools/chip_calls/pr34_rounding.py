"""PR 34's check that cell 6's gap between program and reference (0.08 of a row's spread, three
times cell 4's) is rounding that compounds over the loop, and not a fault that shows only at
the timed length: at the published widths and 4,096 tokens, (1) the program with float32
parameters (every product exact) against the reference, 8 layers deep and all four passes,
which has to agree to about 1e-5; (2) the program as it runs (bfloat16) at the full depth
with 1, 2 and 4 passes, each against the reference with the same count. One JSON line each.

    chiprun -- python3 tools/chip_calls/pr34_rounding.py [seed] ;  REHEARSAL=1 JAX_PLATFORMS=cpu rehearses it
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from sparkdl_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import lm_weights, model  # noqa: E402
from benchmarks.comparers import logprob_rows_looped as comparer  # noqa: E402
from benchmarks.comparers.logprob_rows import row_gaps  # noqa: E402
from benchmarks.drivers.token_stream_looped import model_function  # noqa: E402

seed = int(sys.argv[1]) if len(sys.argv) > 1 else 2147686101
config = model.load_config(os.path.join(ROOT, "benchmarks/configs/ouro_2p6b.json"))
traffic = model.load_config(os.path.join(ROOT, "benchmarks/traffic/tokens_stream_4k.json"))
if os.environ.get("REHEARSAL"):
    traffic.update(traffic["rehearsal"])
    config.update(traffic["config"])
length = traffic["row_tokens"]
tokens = lm_weights.token_rows(seed, 2, length, config["vocab_size"], traffic["zipf_exponent"])
depth = config["num_hidden_layers"]
cases = [("float32", min(8, depth), config["total_ut_steps"])] + [("bfloat16", depth, n) for n in (1, 2, 4)]
for dtype, layers, passes in cases:
    case = dict(config, num_hidden_layers=layers, total_ut_steps=passes)
    weights = lm_weights.make_weights(case, seed)
    sound, sound_pdf = comparer.reference_outputs(case, weights, tokens)
    if dtype == "float32":
        weights = {k: v.astype(jnp.float32) for k, v in weights.items()}
    out = model_function(case, weights, length)({"tokens": tokens})
    gaps = row_gaps(np.asarray(out["logprobs"]), sound)
    print(json.dumps({"parameters": dtype, "layers": layers, "passes": passes, "seed": seed,
                      "centred_err": gaps.tolist(),
                      "exit_pdf_err_max": float(np.abs(np.asarray(out["exit_pdf"]) - sound_pdf).max())}),
          flush=True)
    weights = out = None
