"""BENCHMARK.json and the files it names, held to the benchmark's
contract as far as a file check can: what a later PR's added entry must
satisfy too."""

import json
import os
import re

import pytest

import harness
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(bench["command"]) <= 32
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    # 2 + 14 runs a cell, each run_seconds + 60, 180 more a cell, 1200
    # spare: the full 24 cells have to fit into 43200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith("benchmark/")
        cfg = load(ROOT, c["file"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg and key in cfg["reduced_why"]
            assert not key.endswith(("_dim", "_rank"))
        assert cfg["guarantees"], "a deployment states its guarantees"
        for group in ("env", "env_traced"):
            for var, spec in cfg[group].items():
                assert spec["why"], f"{var} has no reason"


REFERENCE_CONTRACT = ("set_of", "shard_count", "shard_file_size",
                      "reference_shards", "shards_touched", "read_idx",
                      "needle_id_of", "record_length")


def test_codec_blocks(bench):
    """Every configuration says what code its volumes are under and names
    the plain reference that holds them to it."""
    for c in bench["configs"]:
        codec = load(ROOT, c["file"])["codec"]
        assert set(codec) == {"reference", "tag", "family", "data_shards",
                              "parity_shards", "large_block_bytes",
                              "small_block_bytes"}
        assert NAME.match(codec["tag"]) and NAME.match(codec["reference"])
        ref = harness.reference_of(codec)  # the file is there, the tag's
        # set is the block's
        for name in REFERENCE_CONTRACT:
            assert callable(getattr(ref, name, None)), \
                f"{codec['reference']}.py has no {name}()"
        assert ref.set_of(codec["tag"]) == (codec["data_shards"],
                                            codec["parity_shards"])
        assert ref.shard_count(codec) == codec["data_shards"] + \
            codec["parity_shards"]
        assert codec["tag"].startswith(codec["family"] + "_")
        assert 0 < codec["small_block_bytes"] <= codec["large_block_bytes"]


def test_no_module_but_the_reference_knows_a_code():
    """`harness.py`, `run.py`, the drivers and the readers take the shard
    set from the cell's module and block."""
    knows = re.compile(r"^import reference|^from reference |reference\.[KM]\b"
                       r"|\b14\b", re.M)
    for sub in ("", "drivers", "readers"):
        d = os.path.join(BENCH, sub)
        for name in sorted(os.listdir(d)):
            if name.endswith(".py") and name != "reference.py":
                with open(os.path.join(d, name)) as f:
                    found = knows.findall(f.read())
                assert not found, f"{sub}/{name}: {found}"


def test_workloads(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    configs = {c["name"]: load(ROOT, c["file"]) for c in bench["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        assert configs[w["config"]]["chips"] == w["chips"]
        mix = load(BENCH, "traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           mix["driver"] + ".py"))


def cells_of(metric, bench):
    return set(metric.get("workloads", [w["name"] for w in
                                        bench["workloads"]]))


def test_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layer = {m["name"]: m for m in bench["per_layer"]}
    assert len(e2e) == len(bench["end_to_end"]) <= 16
    assert len(layer) == len(bench["per_layer"]) <= 128
    assert not set(e2e) & set(layer)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    all_cells = {w["name"] for w in bench["workloads"]}
    for m in e2e.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in layer.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and one_line(m["layer"])
        assert m["moves"] in e2e
        assert cells_of(m, bench) <= cells_of(e2e[m["moves"]], bench)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        spec = load(BENCH, "layer_metrics", m["name"] + ".json")
        assert spec["name"] == m["name"]
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    for m in (*e2e.values(), *layer.values()):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert cells_of(m, bench) <= all_cells
    for cell in all_cells:
        assert sum(cell in cells_of(m, bench) for m in e2e.values()) >= 2
        assert any(cell in cells_of(m, bench) for m in layer.values())


def test_tables():
    peaks = load(BENCH, "peaks.json")
    assert peaks["source"] and "TPU v5 lite" in peaks["devices"]
    table = harness.kernel_table()
    assert set(table) == {"why", "device_plane", "op_lines", "kernels"}
    for pattern in (table["device_plane"], *table["op_lines"]):
        re.compile(pattern)
    # tests/test_tpu_aot.py reads these three from kernels.json itself
    assert {"gf_apply", "gf_reconstruct", "gf_apply_batch"} <= \
        set(load(BENCH, "kernels.json")["kernels"])
    for name, spec in table["kernels"].items():
        assert NAME.match(name)
        assert set(spec) - {"what"} == {"line", "patterns", "bound",
                                        "perf_kernels"}
        assert spec["bound"] in peaks["devices"]["TPU v5 lite"]
        for pattern in (spec["line"], *spec["patterns"]):
            re.compile(pattern)


def test_a_kernel_can_be_a_file_of_its_own(tmp_path, monkeypatch):
    shared = load(BENCH, "kernels.json")
    with open(tmp_path / "kernels.json", "w") as f:
        json.dump(shared, f)
    os.mkdir(tmp_path / "kernels")
    spec = dict(shared["kernels"]["gf_apply"], what="a kernel a PR brings",
                patterns=["^%_lrc_repair(\\.\\d+)? = "])
    with open(tmp_path / "kernels" / "lrc_repair.json", "w") as f:
        json.dump(dict(spec, name="lrc_repair"), f)
    monkeypatch.setattr(harness, "BENCH", str(tmp_path))
    table = harness.kernel_table()
    assert set(table["kernels"]) == set(shared["kernels"]) | {"lrc_repair"}
    assert table["kernels"]["lrc_repair"] == spec
    # a file may not take a name that is there, nor name another kernel
    with open(tmp_path / "kernels" / "gf_apply.json", "w") as f:
        json.dump(dict(spec, name="gf_apply"), f)
    with pytest.raises(harness.BenchFailure, match="gf_apply"):
        harness.kernel_table()


def test_a_roofline_has_its_kernels_file(bench):
    table = harness.kernel_table()["kernels"]
    for m in bench["per_layer"]:
        if m["name"].endswith("_roofline"):
            spec = load(BENCH, "layer_metrics", m["name"] + ".json")
            assert m["name"] == spec["params"]["kernel"] + "_roofline"
            assert spec["params"]["kernel"] in table


def test_file_names_under_paths():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x not in (".cache", "work", "out",
                                                "__pycache__",
                                                ".pytest_cache")]
        for name in files:
            rel = os.path.relpath(os.path.join(d, name), ROOT)
            assert ok.match(rel), rel
