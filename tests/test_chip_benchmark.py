"""The chip benchmark's own tests, collected under ``pytest tests/``.

``benchmarks/chip/tests/`` lives beside the benchmark (the one directory a
benchmark PR may write to), where tier-1 never looked: until this file
nothing in tier-1 guarded the reducer, the FLOP count, the contract line
or the readers.  Every test function of those files is re-exported here
under its own name, so each is a case of its own (parametrised ones keep
their cases); none is marked slow.
"""

import importlib.util
import os

_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip", "tests")


def _collect():
    for fname in sorted(os.listdir(_TESTS)):
        if not (fname.startswith("test_") and fname.endswith(".py")):
            continue
        spec = importlib.util.spec_from_file_location(
            "chipbench_tests_" + fname[:-3], os.path.join(_TESTS, fname))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for name, obj in vars(mod).items():
            if name.startswith("test_") and callable(obj):
                assert name not in globals(), f"two tests named {name}"
                globals()[name] = obj


_collect()
