"""Pins that a new cell cannot satisfy, set aside by name until a
``benchmark`` PR turns them into rules.

``test_loop_cell.py`` (PR 44) holds the tenth cell by COUNT (ten cells, seven
configurations) and by POSITION (its configuration, its cell and its three
metrics last in their lists).  ``BENCHMARK.json`` may only be appended to and
no PR but a ``benchmark`` one may edit a file under its ``paths``, so the
next cell (PR 47's ``granite-4.0-h-small-ep2-d10.ragdoc-backlog``) fails all
five whatever it does.  They are expected failures here, not deleted and not
edited; the rules they wrap (``test_manifest.py``'s four, run on the manifest
as it stands) are held by ``test_seq_hybrid_cell.py`` for the eleventh cell,
and what the tenth cell reports is still read from its own lists there.  A
``benchmark`` PR removes the pins and this file (PERF.md section 7)."""
import pytest

PINNED = {
    "test_loop_cell.py::test_the_cell_reports_what_issue_44_lists",
    "test_loop_cell.py::test_the_manifest_rules_hold_with_the_tenth_cell",
}


def pytest_collection_modifyitems(items):
    for item in items:
        where = item.nodeid.split("/")[-1].split("[")[0]
        if where in PINNED:
            item.add_marker(pytest.mark.xfail(
                reason="pins the tenth cell's count and position; an "
                       "eleventh cell exists since PR 47", strict=False))
