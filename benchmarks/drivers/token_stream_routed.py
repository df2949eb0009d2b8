"""Not a driver: ``program_lm.model_function`` under the name that ``tests/test_axk1.py`` and
scripts under ``tools/chip_calls/`` import. No traffic file names it; ``drivers/token_stream.py``
runs every language-model configuration."""

from benchmarks.program_lm import model_function  # noqa: F401
