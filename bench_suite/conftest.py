"""The benchmark's own tests: a row-sharded cell, shrunk to a few thousand
rows by ``tests/tiny.py`` for the tests that run every cell of
BENCHMARK.json (``test_rehearsal``, ``test_control``), draws its base in
row blocks of :data:`TINY_ROW_BLOCK` rows instead of
``reference.ROW_BLOCK`` (2^20), so that its rows are a whole number of
blocks on the devices such a test gives it."""

import pytest

#: rows per generator block of a shrunk row-sharded cell
TINY_ROW_BLOCK = 256


@pytest.fixture(autouse=True)
def _tiny_row_blocks(request, monkeypatch):
    callspec = getattr(request.node, "callspec", None)
    name = callspec.params.get("name") if callspec else None
    if not isinstance(name, str):
        return
    from bench_suite import reference, spec

    try:
        cell = spec.find_cell(name)
    except KeyError:
        return
    if cell.config["data"].get("placement") == reference.ROW_SHARDED:
        monkeypatch.setattr(reference, "ROW_BLOCK", TINY_ROW_BLOCK)
