"""The room a ``model_config`` PR needs (ISSUE 39): a seventh serving cell,
its configuration and its per-layer metrics, added to ``BENCHMARK.json`` as
entries and to a copy of ``benchmark/`` as files, pass every rule the
benchmark's tests hold the manifest to; and the rules still bite a cell that
is left out of a list it belongs in."""
import copy
import json
import os
import shutil

import pytest

from benchmark import run as bench_run
from tests.benchmark import (test_gap_anatomy, test_hybrid_cell,
                             test_latent_cell, test_manifest)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HELD = ("moe_local_pair_share", "moe_held_touched_share")
CONFIG, CELL = "seventh-ep8-d24", "seventh-ep8-d24.longctx-backlog"
OF = "kanana-2-30b-a3b-ep8-d24"       # whose files the new one copies


def _manifest_rules(manifest):
    """Every test that reads ``BENCHMARK.json``, on this manifest."""
    test_manifest.test_keys_names_units(manifest)
    test_manifest.test_moves_and_coverage(manifest)
    test_manifest.test_files_exist(manifest)
    test_manifest.test_config_files_agree_with_what_is_run(manifest)
    test_gap_anatomy.test_the_manifest_lists_the_four_for_the_serving_cells_alone(
        manifest)
    test_hybrid_cell.test_the_hybrid_metrics_list_the_new_cell_alone(manifest)
    test_hybrid_cell.test_a_cut_configuration_states_what_it_cut_and_names_its_reference(
        manifest)
    test_latent_cell.test_the_cell_reports_what_issue_32_lists(manifest)


@pytest.fixture
def room(tmp_path, monkeypatch):
    """A copy of the benchmark with a seventh serving cell the way a
    ``model_config`` PR adds one: no file that is there edited, a
    configuration that holds a share of its experts in a file of its own, a
    ``serve-backlog`` cell, two readers; the rules read the copy.  Returns
    ``manifest(where)``: the entries added, the two per-layer ones at
    position ``where`` of the list."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for module in (bench_run, test_manifest, test_gap_anatomy,
                   test_hybrid_cell, test_latent_cell):
        monkeypatch.setattr(module, "ROOT", str(tmp_path))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        parent = json.load(f)
    b = tmp_path / "benchmark"
    shutil.copy(b / "configs" / (OF + ".json"),
                b / "configs" / (CONFIG + ".json"))
    for name in ("seventh_state_share", "seventh_decode_roofline"):
        (b / "layer_metrics" / (name + ".py")).write_text(
            "def read(record):\n    return None\n")

    def manifest(where):
        m = copy.deepcopy(parent)
        was = next(c for c in m["configs"] if c["name"] == OF)
        assert "n_routed_experts" in was["reduced"]
        m["configs"].append({**was, "name": CONFIG,
                             "file": f"benchmark/configs/{CONFIG}.json"})
        m["workloads"].append({"name": CELL, "config": CONFIG,
                               "traffic": "longctx-backlog", "chips": 1,
                               "why": "a seventh serving cell"})
        serving = next(e for e in m["end_to_end"]
                       if e["name"] == "serve_tokens_per_s")
        every = set(serving["workloads"])
        for e in m["per_layer"]:
            if set(e.get("workloads", ())) >= every or e["name"] in HELD:
                e["workloads"].append(CELL)
        serving["workloads"].append(CELL)   # setup_s has no list: covers it
        at = {"last": len(m["per_layer"]),
              "before the gap readers": [e["name"] for e in m["per_layer"]]
              .index(test_gap_anatomy.NAMES[0])}[where]
        m["per_layer"][at:at] = [
            {"name": "seventh_state_share", "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "paged forward",
             "moves": "serve_tokens_per_s", "workloads": [CELL]},
            {"name": "seventh_decode_roofline", "unit": "%",
             "better": "higher", "source": "device_trace",
             "layer": "paged forward", "moves": "serve_tokens_per_s",
             "workloads": [CELL]}]
        return m

    return manifest


@pytest.mark.parametrize("where", ["last", "before the gap readers"])
def test_a_seventh_serving_cell_is_entries_and_files_alone(room, where):
    manifest = room(where)
    _manifest_rules(manifest)
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed >= set(test_gap_anatomy.NAMES) | set(HELD) | {
        "seventh_state_share", "seventh_decode_roofline"}


def test_the_parents_manifest_passes_in_the_copy(room):
    """The fixture's copy and patched roots change nothing by themselves."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        _manifest_rules(json.load(f))


@pytest.mark.parametrize("left_out_of,rule", [
    ("gap_host_share",
     test_gap_anatomy.test_the_manifest_lists_the_four_for_the_serving_cells_alone),
    ("serve_tokens_per_s",
     test_gap_anatomy.test_the_manifest_lists_the_four_for_the_serving_cells_alone),
    ("moe_held_touched_share",
     test_hybrid_cell.test_the_hybrid_metrics_list_the_new_cell_alone),
], ids=["a gap reader", "the capacity metric", "a held-share reader"])
def test_the_rules_still_bite(room, left_out_of, rule):
    """A serving cell left out of a list it belongs in fails the rule that
    holds that list."""
    manifest = room("last")
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        if m["name"] == left_out_of:
            m["workloads"].remove(CELL)
    with pytest.raises(AssertionError):
        rule(manifest)
