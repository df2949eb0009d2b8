"""PR 36's probes that need no cell.

(1) Does ``jax.device_put`` of a parameter tree return before the bytes have landed? GB (default 2) of float32 in
8 leaves: seconds inside ``device_put``, then seconds until ``block_until_ready`` returns, twice (the first
placement also grows the allocator), then the same tree through ``ModelFunction.device_params()`` beside
``ship.params_place_seconds``.
(2) A second device-batch size forced after a warm pass: TestNet through two ``BatchRunner``s over one
``ModelFunction``; ``compile.programs`` and the reader of ``setup.program_compiles`` before, after the first size,
after a steady rerun, after the second size.
(3) With ALONE="<cells>": each token cell's program traced and lowered from shapes alone (no weights, no device
memory, nothing compiled or run) in THIS process, which does nothing else: the seconds the listener hears, to set
beside the same program's ``compile.trace_seconds`` / ``lower_seconds`` inside the benchmark's process, where PR 31
found a traced operation ten times dearer (PERF.md, section 7).

With TOPO=v5e:2x2 and JAX_PLATFORMS=cpu, in the sandbox, part (3) alone, for a described chip (the kernels lowered
for Mosaic and not for the interpreter): a count of the sandbox's host, never a number of the chip's."""
import importlib
import inspect
import json
import os
import sys
import time

sys.path.insert(0, ".")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.readers import registry_sum  # noqa: E402
from sparkdl_tpu.graph.function import ModelFunction  # noqa: E402
from sparkdl_tpu.obs import compile_log, default_registry  # noqa: E402
from sparkdl_tpu.runtime.runner import BatchRunner  # noqa: E402

REG = default_registry()


def placement(gb: float) -> None:
    n = int(gb * 1e9 / 4 / 8)
    tree = {f"w{i}": np.full((n,), float(i), np.float32) for i in range(8)}
    nbytes = sum(v.nbytes for v in tree.values())
    for attempt in (1, 2):
        t0 = time.perf_counter()
        placed = jax.device_put(tree)
        t1 = time.perf_counter()
        jax.block_until_ready(placed)  # sparkdl-lint: allow[H1] -- the probe's own question: when have the bytes landed
        t2 = time.perf_counter()
        print(f"device_put {attempt}: {nbytes / 1e9:.3f} GB, inside the call {t1 - t0:.4f} s, then until the "
              f"bytes had landed {t2 - t1:.4f} s ({nbytes / 1e9 / (t2 - t0):.2f} GB/s over both)")
        del placed
    mf = ModelFunction(lambda p, x: {"y": x["input"] + p["w0"][:4]}, tree, {"input": ((4,), np.float32)},
                       name="probe_place")
    before = REG.snapshot().get("ship.params_place_seconds", 0.0)
    t0 = time.perf_counter()
    placed = mf.device_params()
    t1 = time.perf_counter()
    jax.block_until_ready(placed)  # sparkdl-lint: allow[H1] -- the probe's own question: when have the bytes landed
    t2 = time.perf_counter()
    print(f"device_params(): call {t1 - t0:.4f} s, ship.params_place_seconds moved "
          f"{REG.snapshot().get('ship.params_place_seconds', 0.0) - before:.4f} s, then until landed {t2 - t1:.4f} s")


def second_batch_size() -> None:
    from sparkdl_tpu.models.zoo import getModelFunction

    def programs():
        spec = bench_run._metric_spec("setup.program_compiles")
        return json.dumps({"compile.programs": REG.snapshot().get("compile.programs"),
                           "setup.program_compiles": registry_sum.read({}, spec["params"])})

    mf = getModelFunction("TestNet", featurize=True)
    (name, (shape, dtype)), = mf.input_signature.items()
    rows = {name: np.zeros((128,) + tuple(shape), dtype)}
    print("before any run", programs())
    first = BatchRunner(mf, batch_size=64)
    first.run(rows)
    print("after the warm pass at device batch 64", programs())
    first.run(rows)
    print("after a steady pass at 64", programs())
    BatchRunner(mf, batch_size=32).run(rows)
    print("after a pass at device batch 32, forced", programs())
    print("phases", json.dumps({k: v for k, v in compile_log().phases().items() if v["instrumented"]}))


def alone(cells, place: dict) -> None:
    bench = bench_run._load("BENCHMARK.json")
    for cell_name in cells:
        cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
        config = bench_run._load(next(c for c in bench["configs"] if c["name"] == cell["config"])["file"])
        traffic = bench_run._load(f"benchmarks/traffic/{cell['traffic']}.json")
        module = importlib.import_module(f"sparkdl_tpu.models.{config['program']['module']}")
        if "router_width" in config:  # as program_lm: the program's num_experts is the router's width
            config = dict(config, num_experts=config["router_width"])
        tree = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, **place),
                            module.param_shapes(config))
        extra = ({"routing_stats": True}
                 if "routing_stats" in inspect.signature(module.model_function).parameters else {})
        tokens = int(traffic["row_tokens"])
        mf = module.model_function(config, tree, seq_len=tokens, **extra)
        before = REG.snapshot()
        t0 = time.perf_counter()
        mf.jitted().lower(tree, {"tokens": jax.ShapeDtypeStruct(
            (int(traffic["device_batch"]), tokens), jnp.int32, **place)})
        wall = time.perf_counter() - t0
        after = REG.snapshot()
        moved = {k: after[k] - before.get(k, 0.0) for k in ("compile.trace_seconds", "compile.lower_seconds")}
        entry = compile_log().phases().get(mf.jitted()._jax_name, {})
        print(f"alone {cell_name}: lower() {wall:.3f} s by the host's clock", json.dumps(moved),
              "nested events", entry.get("nested"), "of", round(entry.get("nested_s", 0.0), 3), "s")


print("device", jax.devices()[0].platform, jax.devices()[0].device_kind, len(jax.devices()))
topology = os.environ.get("TOPO")
if topology:  # no chip: describe one, and let the kernels lower for it
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from sparkdl_tpu.ops import attention, gated_delta, moe
    for ops in (attention, gated_delta, moe):
        ops._use_interpreter = lambda: False
    described = topologies.get_topology_desc(platform="tpu", topology_name=topology).devices[0]
    print("described", described.device_kind, "(a count of this host, no chip)")
    alone(os.environ.get("ALONE", "").split(), {"sharding": SingleDeviceSharding(described)})
else:
    placement(float(os.environ.get("GB", "2")))
    second_batch_size()
    alone(os.environ.get("ALONE", "").split(), {})
