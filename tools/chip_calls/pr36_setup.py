"""Run a benchmark entry point (run.py or traced.py) unchanged and print, on stderr, what the compile log
heard of set-up (PR 36): at the harness's "warm pass" mark, which ends set-up and comes before the window
and long before the reference compiles, ``CompileLog.phases()`` and the ``compile.*`` / ``ship.params_*``
counters (``setup_end {...}``), and the same counters once the run is over (``process_end {...}``: the
window and the reference besides). On a program from before PR 36 the log has no ``phases`` and the
counters are not there: the lines say so and the run goes on."""
import json
import runpy
import sys

script, sys.argv = sys.argv[1], sys.argv[1:]


def _counters():
    from sparkdl_tpu.obs import default_registry
    return {k: v for k, v in sorted(default_registry().snapshot().items())
            if k.startswith(("compile.", "ship.params_"))}


def _hook():
    from benchmarks import harness
    mark = harness.Run.mark

    def marked(self, what):
        mark(self, what)
        if what == "warm pass":
            from sparkdl_tpu.obs import compile_log
            phases = getattr(compile_log(), "phases", None)
            print("setup_end " + json.dumps({
                "marks": self.marks, "counters": _counters(),
                "phases": phases() if phases else "no phases(): a program from before PR 36"}),
                file=sys.stderr, flush=True)

    harness.Run.mark = marked


sys.path.insert(0, ".")
_hook()
code = 0
try:
    runpy.run_path(script, run_name="__main__")
except SystemExit as e:
    code = e.code or 0
print("process_end " + json.dumps({"counters": _counters()}), file=sys.stderr, flush=True)
sys.exit(code)
