"""The benchmark finds configurations, traffic mixes and per-layer
metrics by name, and a new one of each is a new file."""
import json
import shutil

import pytest

from bench_cells import ROOT, SPEC
from bench import registry


def test_every_cell_resolves_its_files():
    names = {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert w["config"] in names
        cfg = registry.load_config(w["config"], SPEC)
        assert cfg["name"] == w["config"]
        assert registry.load_traffic(w["traffic"])["driver"] in ("learned", "sweep")
        e2e = {m["name"] for m in registry.end_to_end(SPEC, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = registry.per_layer(SPEC, w["name"])
        assert layer, w["name"]
        for m in layer:
            assert m["moves"] in e2e
            assert callable(registry.load_metric(m["name"]).read)


def test_config_files_hold_their_source_and_cut():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] == []
        assert (ROOT / cfg["pretrain"]["table"]).is_file()


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        registry.cell(SPEC, "no-such-cell")
    with pytest.raises(KeyError):
        registry.load_config("no-such-config", SPEC)


def test_a_new_cell_is_new_files_only(tmp_path):
    """Add a configuration, a traffic mix and a metric beside copies of
    the committed files; the committed ones are read unchanged."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    before = {p.relative_to(tmp_path): p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    spec = json.loads(json.dumps(SPEC))
    cfg = registry.load_config("gpgpu-suite", SPEC)
    cfg.update(name="dummy-config", workloads=[["ATAX"]])
    (tmp_path / "bench/configs/dummy-config.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/dummy-traffic.json").write_text(json.dumps(
        {"driver": "sweep", "trace_seconds": 1.0, "lanes": [["lru", "demand", 1.25]], "sample": {"workloads": 1}}))
    (tmp_path / "bench/metrics/dummy_metric.py").write_text("def read(view):\n    return 42.0\n")
    spec["configs"].append({"name": "dummy-config", "source": cfg["source"],
                            "file": "bench/configs/dummy-config.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "dummy.cell", "config": "dummy-config", "traffic": "dummy-traffic",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "dummy_metric", "unit": "%", "better": "higher", "source": "device_trace",
                              "layer": "device", "moves": "sweep_accesses_per_s", "workloads": ["dummy.cell"]})
    spec["end_to_end"][2]["workloads"].append("dummy.cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    spec2 = registry.load_benchmark(tmp_path)
    assert registry.cell(spec2, "dummy.cell")["traffic"] == "dummy-traffic"
    assert registry.load_config("dummy-config", spec2, tmp_path)["workloads"] == [["ATAX"]]
    assert registry.load_traffic("dummy-traffic", tmp_path)["lanes"] == [["lru", "demand", 1.25]]
    assert registry.load_metric("dummy_metric", tmp_path).read(None) == 42.0
    assert [m["name"] for m in registry.per_layer(spec2, "dummy.cell")] == ["dummy_metric"]
    after = {p.relative_to(tmp_path): p.read_bytes() for p in (tmp_path / "bench").rglob("*")
             if p.is_file() and p.relative_to(tmp_path) in before}
    assert after == before
