"""One test of the benchmark's pins the manifest as PR 25 left it (28
per-layer metrics, the four-chip cell last), so it fails as soon as a
PR appends a cell, and a PR that appends may not edit it.  Until a
`benchmark` PR relaxes the pin it is expected to fail, strictly: the
day it passes again this file has to go.  What it guards (PR 25's
entries in place, later ones after them) is asserted in a form that
survives an append by ``tests/test_zipf_cell.py``."""
import pytest

PINNED = "test_the_manifest_appends_what_issue_25_names"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == PINNED:
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="pins PR 25's manifest; PR 27 appended a cell and "
                       "five metrics (PERF.md Open question 13)"))
