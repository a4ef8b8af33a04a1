"""The benchmark's end-to-end reduction (`benchmarks/chip/lib/endtoend.py`),
held to the cases its own rehearsal keeps: this file imports
`benchmarks/chip/rehearsal/check_endtoend.py` as it stands and runs each of
its `CASES` as a test of the repo, so that a change to the percentile, the
slowest-fifth mean, the window's edges or the kept samples fails tier-1 and
not only `rehearse.sh`. Nothing under `benchmarks/chip/` is edited."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_check():
    """The rehearsal file puts `benchmarks/chip` on the path itself (it
    imports `lib.endtoend` as `run.py` does); take that entry and the
    `lib` package out again once it is loaded, as
    tests/test_host_spans.py does, so no later test of this worker
    resolves through them."""
    path = os.path.join(ROOT, "benchmarks", "chip", "rehearsal",
                        "check_endtoend.py")
    spec = importlib.util.spec_from_file_location("chip_check_endtoend",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    before = list(sys.path)
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path[:] = before
        for name in [m for m in sys.modules
                     if m == "lib" or m.startswith("lib.")]:
            del sys.modules[name]
    return mod


CASES = _load_check().CASES


def test_the_rehearsal_still_holds_its_fourteen_cases():
    assert len(CASES) == 14
    assert len({name for name, _ in CASES}) == 14


@pytest.mark.parametrize("name,case", CASES, ids=[n for n, _ in CASES])
def test_endtoend_case(name, case):
    case()
