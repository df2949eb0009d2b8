"""PR 37's look at the expert block's layout alone, outside the benchmark: the tree's
`ops/moe.py::grouped_layout`, which finds an assignment's row by counting the earlier ones of
its expert (a product of ones with a triangle, block by block), against the parent's
(`.bench_parent`: git archive of e0f2849), which sorts the assignments by expert, looks twice
into a table by expert and scatters twice.

    chiprun -- python3 tools/chip_calls/pr37_layout.py [name=value ...]

At cell 4's shapes (16,384 tokens, 10 choices of 512 experts, 128 held, tiles of 128, experts of
2,048 x 512) and cell 5's (8 of 192, 12 held, tiles of 256, 7,168 x 2,048), seeded routing drawn
evenly, with the even share of the assignments held (25% and 6.25%), all of them and none. For
each: both layouts' ms a call and whether every array is the parent's (`row_token`,
`tile_expert`, `tiles_used`, `counts`, `is_held` whole, `dest` where `is_held`); at the even
share the device time of each by instruction; then `held_experts_ffn` whole, both sides, and
whether the answers agree to the last bit. Exit code 1 if anything differs. `name=value` sets
a module constant of the tree's `ops/moe.py` for a run after the tree as it is
(`_COUNT_BLOCK=128`). `CELLS=4` keeps to one cell; a cell that is seven numbers
(`CELLS=300,3,8,3,8,64,32`: tokens, choices, the router's width, experts held, tile, D, F)
rehearses the script on the CPU.

    chiprun -- python3 tools/chip_calls/pr37_layout.py step qwen3next|axk1 [seed]

One cell's model function over seeded weights, built once with the parent's layout and once
with the tree's over one set of weights (as `pr35_profile.py` did for attention): every step
timed alone, whether every answer is the parent's to the last bit, then two steps under the
profiler: device time by scope, and the instructions of one expert block. `ROWS=4 REHEARSAL=1
JAX_PLATFORMS=cpu` rehearses it at the traffic file's rehearsal sizes.

    chiprun -- python3 tools/chip_calls/pr37_layout.py lead

What is left of the layout is its one scatter (163,840 indices at 4.6 ns). A lead, not the
tree's: `row_token` with no scatter, from one sort of the assignments packed a word each (expert
above, place below) and one window of the sorted tokens a tile (a gather of as many lookups as
the buffer has tiles). Its ms a call beside the tree's layout, and whether `row_token` is the
tree's.
"""
import functools
import importlib.util
import json
import os
import re
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from sparkdl_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import tracing  # noqa: E402
from sparkdl_tpu.ops import moe  # noqa: E402

SHAPES = {  # cell: tokens, choices, the router's width, experts held, tile, D, F
    "4": (16384, 10, 512, 128, 128, 2048, 512),
    "5": (16384, 8, 192, 12, 256, 7168, 2048),
}
LOG_DIR = os.path.join(ROOT, ".bench_trace")
NAMES = ("row_token", "dest", "is_held", "tile_expert", "tiles_used", "counts")


def load_parent():
    path = os.path.join(ROOT, ".bench_parent/sparkdl_tpu/ops/moe.py")
    spec = importlib.util.spec_from_file_location("parent_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def routing(seed, n, k, pool):
    """``k`` distinct experts a token, drawn evenly from ``pool`` (a range of the
    router's width): the share held is the pool's overlap with the experts held."""
    rng = np.random.default_rng(seed)
    lo, hi = pool
    experts = lo + np.argsort(rng.random((n, hi - lo)), axis=1)[:, :k]
    weights = rng.random((n, k)) + 0.1
    weights /= weights.sum(axis=1, keepdims=True)
    return jnp.asarray(experts, jnp.int32), jnp.asarray(weights, jnp.float32)


def timed(fn, args, calls=20):
    out = fn(*args)
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the compile ends here
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the timed calls end here
    return out, (time.perf_counter() - t) / calls * 1e3


def device_time(run, steps):
    """``(seconds a step by instruction, events by instruction, the XLA Modules events in
    ms)`` of ``steps`` calls of ``run`` under the profiler; nothing on the CPU."""
    jax.profiler.start_trace(LOG_DIR)
    out = [run(i) for i in range(steps)]
    jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: the traced calls end here
    jax.profiler.stop_trace()
    try:
        data = jax.profiler.ProfileData.from_file(tracing.find_trace_file(LOG_DIR))
        planes = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    except FileNotFoundError:  # a rehearsal on the CPU
        planes = []
    seconds, count, modules = {}, {}, []
    for plane in planes:
        for line in plane.lines:
            if line.name == "XLA Modules":
                modules += [round(e.duration_ns * 1e-6, 3) for e in line.events]
            if line.name == "XLA Ops":
                for e in line.events:
                    m = re.match(r"^%?([\w.\-]+) = ", e.name)
                    key = m.group(1) if m else e.name
                    seconds[key] = seconds.get(key, 0.0) + e.duration_ns * 1e-9 / steps
                    count[key] = count.get(key, 0) + 1
    return seconds, count, modules


_AS_IT_IS = {}


def apply(setting):
    """The tree's constants as they are, then ``setting``'s over them."""
    for name, value in _AS_IT_IS.items():
        setattr(moe, name, value)
    for pair in filter(None, setting.split(",")):
        name, value = pair.split("=")
        _AS_IT_IS.setdefault(name, getattr(moe, name))
        setattr(moe, name, int(value))
    jax.clear_caches()  # what was traced read the old constants


differences = []


def same_layout(got, expected):
    is_held = np.asarray(expected[2])
    apart = []
    for name, ours, theirs in zip(NAMES, got, expected):
        ours, theirs = np.asarray(ours), np.asarray(theirs)
        if name == "dest":
            ours, theirs = ours[is_held], theirs[is_held]
        if ours.shape != theirs.shape or ours.dtype != theirs.dtype or not np.array_equal(ours, theirs):
            apart.append(name)
    differences.extend(apart)
    return "every array the parent's" if not apart else f"NOT the parent's: {apart}"


def one_cell(cell, parent, settings):
    n, k, width, held, tile, d, f = SHAPES.get(cell) or (int(v) for v in cell.split(","))
    print(f"cell {cell}: {(n, k, width, held, tile, d, f)}", flush=True)
    rng = np.random.default_rng(37)
    x = jnp.asarray(rng.standard_normal((n, d), np.float32))
    w = [jnp.asarray(rng.standard_normal(s, np.float32) / np.sqrt(s[1]), jnp.bfloat16)
         for s in ((held, d, f), (held, d, f), (held, f, d))]
    layout = lambda m: jax.jit(lambda e: m.grouped_layout(e, 0, held, tile))
    whole = lambda m: jax.jit(lambda a, e, p, *ms: m.held_experts_ffn(a, e, p, *ms, first=0, tile=tile)[0])
    cases = {"even share held": (0, width), "everything held": (0, held), "nothing held": (held, width)}
    for case, pool in cases.items():
        experts, weights = routing(37, n, k, pool)
        expected, parent_ms = timed(layout(parent), (experts,))
        print(f"  {case} ({100 * float(jnp.mean(expected[2])):.2f}% of {n * k} assignments, "
              f"{int(expected[4])} tiles in use): the parent's layout {parent_ms:8.3f} ms", flush=True)
        y_parent, parent_block_ms = timed(whole(parent), (x, experts, weights, *w), calls=10)
        for setting in settings:
            apply(setting)
            got, ms = timed(layout(moe), (experts,))
            y, block_ms = timed(whole(moe), (x, experts, weights, *w), calls=10)
            bits = bool(jnp.array_equal(y, y_parent))
            differences.extend([] if bits else ["held_experts_ffn"])
            print(f"    tree {setting:18s}: layout {ms:8.3f} ms, {same_layout(got, expected)};   "
                  f"held_experts_ffn {block_ms:8.3f} ms (the parent's {parent_block_ms:8.3f}), "
                  f"the answer the parent's to the last bit: {bits}", flush=True)
        apply("")
        if case != "even share held":
            continue
        for side, m in (("parent", parent), ("tree", moe)):
            fn = layout(m)
            jax.block_until_ready(fn(experts))  # sparkdl-lint: allow[H1] -- a measure tool: the compile ends here
            seconds, count, modules = device_time(lambda i: fn(experts), 5)
            print(f"    {side}'s layout on the device: {sum(seconds.values()) * 1e3:.3f} ms a call "
                  f"(XLA Modules {modules}); instructions of 0.003 ms and more:", flush=True)
            for name, s in sorted(seconds.items(), key=lambda kv: -kv[1]):
                if s * 1e3 >= 0.003:
                    print(f"      {s * 1e3:8.3f} ms  {name}")


def layouts():
    settings = [""] + [a for a in sys.argv[1:] if "=" in a]
    parent = load_parent()
    for cell in os.environ.get("CELLS", "4 5").split():
        one_cell(cell, parent, settings)
    return 1 if differences else 0


def step(cell, seed):
    from benchmarks import lm_weights, program_lm
    from benchmarks.drivers import token_stream_routed
    from sparkdl_tpu.obs import compile_log
    config_name, traffic_name, model_function, block = {
        "axk1": ("axk1_ep16", "tokens_stream_p16", token_stream_routed.model_function, r"SparseMoe_1\b"),
        "qwen3next": ("qwen3next_80b_a3b_ep4", "tokens_stream", program_lm.model_function, r"SparseMoe_3\b"),
    }[cell]
    model_function = functools.partial(model_function, routing_stats=True)
    config = json.load(open(os.path.join(ROOT, f"benchmarks/configs/{config_name}.json")))
    traffic = json.load(open(os.path.join(ROOT, f"benchmarks/traffic/{traffic_name}.json")))
    if os.environ.get("REHEARSAL"):
        traffic.update(traffic["rehearsal"])
        config.update(traffic["config"])
    length, rows = traffic["row_tokens"], int(os.environ.get("ROWS", 8))
    compile_log().arm()
    t = time.perf_counter()
    weights = lm_weights.make_weights(config, seed)
    jax.block_until_ready(weights)  # sparkdl-lint: allow[H1] -- a measure tool: set-up ends here
    print(f"{cell} seed {seed}: weights {time.perf_counter() - t:.1f} s", flush=True)
    tokens = lm_weights.token_rows(seed, rows, length, config["vocab_size"], 1.0)
    tree_layout, answers = moe.grouped_layout, {}
    for side, fn in (("parent", load_parent().grouped_layout), ("change", tree_layout)):
        moe.grouped_layout = fn  # `held_experts_ffn` looks the name up when traced: the first call
        mf = model_function(config, weights, length)
        known = len(compile_log().events())
        t = time.perf_counter()
        outs = [mf({"tokens": tokens[:2]})]
        jax.block_until_ready(outs)  # sparkdl-lint: allow[H1] -- a measure tool: the first call ends here
        print(f"== {side}: first call {time.perf_counter() - t:.1f} s", flush=True)
        scopes = max((e.scopes for e in compile_log().events()[known:] if e.scopes), key=len, default={})
        times = []
        for lo in range(0, rows, 2):
            t = time.perf_counter()
            out = mf({"tokens": tokens[lo:lo + 2]})
            jax.block_until_ready(out)  # sparkdl-lint: allow[H1] -- a measure tool: one step's time
            times.append((time.perf_counter() - t) * 1e3)
            outs.append(out)
        print(f"   steps of 2 rows {[round(x, 2) for x in times]} ms, median {statistics.median(times):.2f}", flush=True)
        answers[side] = [{k: np.asarray(v) for k, v in out.items()} for out in outs]
        same = all(np.array_equal(a[k], b[k]) for a, b in zip(answers[side], answers["parent"]) for k in a)
        differences.extend([] if same else [f"{cell}'s step"])
        print(f"   every answer the parent's to the last bit: {same}")
        stats = jax.devices()[0].memory_stats() or {}
        print("   memory", {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use", "peak_bytes_reserved")})
        steps = 2
        seconds, count, modules = device_time(
            lambda i: mf({"tokens": tokens[2 * i % rows:2 * i % rows + 2]}), steps)
        print(f"   XLA Modules events {modules} ms; device time a step {sum(seconds.values()) * 1e3:.1f} ms")
        by_scope: dict = {}
        for name, s in seconds.items():
            path = [p for p in scopes.get(name, "(no scope)").split("/") if p not in ("while", "body", "cond", "closed_call")]
            kind = "/".join(re.sub(r"_\d+$", "", p) for p in path[:3])
            if "/moe_experts" in kind:  # the three kernels by name, the layout round them as the rest
                kernel = re.match(r"(moe_\w+?)(\.\d+)?$", name)
                kind += "/" + (kernel.group(1) if kernel else "XLA's")
            by_scope[kind] = by_scope.get(kind, 0.0) + s
        for kind, s in sorted(by_scope.items(), key=lambda kv: -kv[1])[:18]:
            print(f"   {s * 1e3:9.2f} ms  {kind}")
        print(f"   instructions under {block!r}, 0.005 ms a run and more (ms a step, runs, ms a run):")
        for name, s in sorted(seconds.items(), key=lambda kv: -kv[1]):
            path = scopes.get(name, "")
            a_run = s * 1e3 * steps / count[name]
            if a_run >= 0.005 and re.search(block, path):
                print(f"   {s * 1e3:9.3f} {count[name]:5d} {a_run:8.3f}  {name:36s} {path[-84:]}")
        mf = outs = out = None
    moe.grouped_layout = tree_layout
    return 1 if differences else 0


def layout_by_windows(experts, first, held, tile):
    """The tree's layout with `row_token` made another way (the tree's scatter is then dead
    code and XLA drops it): the assignments sorted as single words, `key << 18 | place`, give
    the tokens group by group; a tile's rows are one window of them."""
    n, k = experts.shape
    a = n * k
    rows = moe.layout_rows(a, held, tile)
    _, dest, is_held, tile_expert, tiles_used, counts = moe.grouped_layout(experts, first, held, tile)
    key = jnp.where(is_held, experts - first, held).reshape(a)
    packed = jax.lax.sort(key * (1 << 18) + jnp.arange(a, dtype=jnp.int32))
    tokens = jnp.pad((packed & ((1 << 18) - 1)) // k, (0, tile))
    starts = jnp.cumsum(counts) - counts
    padded = -(-counts // tile) * tile
    pad_starts = jnp.cumsum(padded) - padded
    t = jnp.arange(rows // tile, dtype=jnp.int32)
    in_group = t * tile - pad_starts[tile_expert]  # the place in its group of the tile's first row
    windows = jax.vmap(lambda s: jax.lax.dynamic_slice(tokens, (s,), (tile,)))(
        jnp.clip(starts[tile_expert] + in_group, 0, a))
    place = in_group[:, None] + jnp.arange(tile, dtype=jnp.int32)[None, :]
    in_use = (place < counts[tile_expert][:, None]) & (t < tiles_used)[:, None]
    row_token = jnp.where(in_use, windows, n).reshape(rows)
    return row_token, dest, is_held, tile_expert, tiles_used, counts


def lead():
    for cell in os.environ.get("CELLS", "4 5").split():
        n, k, width, held, tile, d, f = SHAPES.get(cell) or (int(v) for v in cell.split(","))
        assert n * k < 1 << 18 and held < 1 << 12
        for case, pool in {"even share held": (0, width), "everything held": (0, held)}.items():
            experts, _ = routing(37, n, k, pool)
            expected, tree_ms = timed(jax.jit(lambda e: moe.grouped_layout(e, 0, held, tile)), (experts,))
            got, ms = timed(jax.jit(lambda e: layout_by_windows(e, 0, held, tile)), (experts,))
            print(f"cell {cell}, {case}: the tree's layout {tree_ms:8.3f} ms; by a sort and a window a tile "
                  f"{ms:8.3f} ms, {same_layout(got, expected).replace('parent', 'tree')}", flush=True)
        fn = jax.jit(lambda e: layout_by_windows(e, 0, held, tile))
        seconds, _, modules = device_time(lambda i: fn(experts), 5)
        print(f"  on the device {sum(seconds.values()) * 1e3:.3f} ms a call (XLA Modules {modules}):")
        for name, s in sorted(seconds.items(), key=lambda kv: -kv[1])[:8]:
            print(f"      {s * 1e3:8.3f} ms  {name}")
    return 1 if differences else 0


if __name__ == "__main__":
    print(jax.devices()[0].device_kind, flush=True)
    if sys.argv[1:] == ["lead"]:
        sys.exit(lead())
    if len(sys.argv) > 1 and sys.argv[1] == "step":
        sys.exit(step(sys.argv[2], int(sys.argv[3]) if len(sys.argv) > 3 else 2147737001))
    sys.exit(layouts())
