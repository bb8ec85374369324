"""Which cases of ``tests/test_cells_cpu.py`` apply to a cell.

That file (PR 24) runs every cell of ``BENCHMARK.json`` on the CPU with one
table of tiny GPT-3 sizes and one table of tiny traffic keyed by loop, both
written in the file. A cell whose configuration is not GPT-shaped cannot
take them. Such a cell brings a test file that runs it end to end at tiny
sizes of its own and names that file in its traffic file under
``cpu_test``; its cases in ``test_cells_cpu.py`` are then skipped with that
pointer, not left failing on a missing table entry. A ``benchmark`` PR that
makes the tables data beside each loop and configuration takes this file
away (PERF.md, Open questions)."""
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _own_tests():
    """Cell name -> the test file its traffic names."""
    with open(os.path.join(HERE, os.pardir, os.pardir,
                           "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    out = {}
    for cell in cells:
        with open(os.path.join(HERE, "traffic",
                               cell["traffic"] + ".json")) as f:
            path = json.load(f).get("cpu_test")
        if path:
            out[cell["name"]] = path
    return out


def pytest_collection_modifyitems(items):
    own = _own_tests()
    for item in items:
        if item.path.name != "test_cells_cpu.py":
            continue
        params = getattr(item, "callspec", None)
        name = params.params.get("name") if params else None
        if name in own:
            item.add_marker(pytest.mark.skip(
                reason=f"{name} runs end to end at its own tiny sizes in "
                       f"{own[name]}"))
