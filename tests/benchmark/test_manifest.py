"""BENCHMARK.json against the benchmark's contract and the files it names."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reporting(metric, cells):
    return set(metric.get("workloads", cells))


def test_keys_names_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }
    for group, keys in allowed.items():
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names)), group
        for e in manifest[group]:
            assert set(e) <= keys, (group, e["name"], set(e) - keys)
            assert NAME.match(e["name"]), e["name"]
            for text in ("why", "layer", "source"):
                if text in e:
                    assert 1 <= len(e[text]) <= 200 and "\n" not in e[text] \
                        and "\t" not in e[text], (e["name"], text)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}
    for c in manifest["workloads"]:
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4)
    assert len(json.dumps(manifest)) < 64 * 1024


def test_moves_and_coverage(manifest):
    cells = [c["name"] for c in manifest["workloads"]]
    e2e = {m["name"]: _reporting(m, cells) for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert _reporting(m, cells) <= e2e[m["moves"]], (
            f"{m['name']} is reported in a cell that does not report "
            f"{m['moves']}")
        assert _reporting(m, cells) <= set(cells)
    for cell in cells:
        assert cell in e2e["setup_s"]
        assert any(cell in r for n, r in e2e.items() if n != "setup_s"), cell
        assert any(cell in _reporting(m, cells)
                   for m in manifest["per_layer"]), cell
    pairs = [(c["config"], c["traffic"]) for c in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [c for c in manifest["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    layers = {}
    for m in manifest["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_files_exist(manifest):
    paths = manifest["paths"]
    used = {c["config"] for c in manifest["workloads"]}
    files = set()
    for c in manifest["configs"]:
        assert c["name"] in used, f"configuration {c['name']} has no cell"
        assert any(c["file"].startswith(p + "/") for p in paths)
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert not re.search(r"(hidden_size|intermediate|_dim$|_rank$|head_size|experts_per_tok)",
                                 key), f"{key}: a width may not be reduced"
    for cell in manifest["workloads"]:
        assert cell["config"] in {c["name"] for c in manifest["configs"]}
        tpath = os.path.join(ROOT, "benchmark", "traffic",
                             cell["traffic"] + ".json")
        with open(tpath) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "traffic_kinds", kind.replace("-", "_") + ".py"))
    from benchmark.run import reader_path

    for m in manifest["per_layer"]:
        assert os.path.isfile(reader_path(m["name"])), m["name"]
    assert reader_path("peak_hbm_gb.serve") == reader_path("peak_hbm_gb.train")
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word


def test_config_files_agree_with_what_is_run(manifest):
    """The published keys at the top of a configuration file and the
    TransformerConfig the harness builds from it say the same sizes."""
    from benchmark.lib import system

    pairs = {"hidden_size": "hidden_size", "num_hidden_layers": "num_layers",
             "num_attention_heads": "num_heads", "vocab_size": "vocab_size",
             "max_position_embeddings": "max_seq_len"}
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        cfg = system.transformer_config(body, rehearse=False)
        for pub, ours in pairs.items():
            assert body[pub] == getattr(cfg, ours), (c["name"], pub)
        ffn = body.get("ffn_dim", body.get("intermediate_size"))
        assert ffn == cfg.intermediate_size
        if "rotary_pct" in body:
            assert cfg.rotary_dim == int(body["rotary_pct"] * cfg.dims_per_head)
        if body.get("activation_function") == "relu":
            assert cfg.activation == "relu"
