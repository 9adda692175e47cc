"""The benchmark's pure reducers, run by tier-1.

Every number in ``PERF_LEDGER.jsonl`` passes through ``benchmark/window.py``
(the timed window), ``benchmark/trace_reduce.py`` (busy and idle time from a
device trace) and ``benchmark/stage_reduce.py`` (booking operations to
stages), the ``part.*`` metrics through ``benchmark/layers/part.py`` and
``benchmark/costs_parts.py`` (bucketing the same rows by the parameter leaf
they multiply), and the ``boot.*`` metrics through
``benchmark/layers/boot.py`` (booking every instant of set-up to one part;
its whole-cell rehearsals are marked ``slow`` and stay the benchmark's).  Their rehearsals live beside the benchmark, under
``benchmark/rehearse/``, which ``pytest tests/`` never collects — so a
rehearsal there can rot unseen (one of ``test_contract.py`` has).  This file
loads the five reducer rehearsals by path and re-exports their tests and
fixtures, so that they run wherever the package's own tests do.  The
rehearsals that run whole cells in child processes (``test_cells.py``,
``test_lm_cell.py``) and ``test_contract.py`` stay the benchmark's own.
"""

import importlib.util
import os

REHEARSE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "rehearse")


def _is_fixture(value) -> bool:
    return (type(value).__name__ == "FixtureFunctionDefinition"
            or hasattr(value, "_pytestfixturefunction"))


for _stem in ("test_window", "test_trace_reduce", "test_stage_reduce",
              "test_part", "test_boot"):
    _spec = importlib.util.spec_from_file_location(
        "benchmark_rehearse_" + _stem, os.path.join(REHEARSE, _stem + ".py"))
    _module = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_module)
    for _name, _value in vars(_module).items():
        if _name.startswith("test_") and callable(_value):
            # the file's stem goes into the name: two files may define one
            _name = f"{_stem}__{_name[len('test_'):]}"
        elif not _is_fixture(_value):
            continue
        if _name in globals():
            raise ImportError(f"{_stem}: {_name} is defined twice")
        globals()[_name] = _value
