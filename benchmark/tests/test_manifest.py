import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

ROOT = manifest.REPO


@pytest.fixture(scope="module")
def bench():
    return manifest.Benchmark()


def _doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_manifest_meets_the_contract(bench):
    manifest.validate(bench.doc)
    assert [w["name"] for w in bench.doc["workloads"]] == [
        "t5base-finetune", "t5base-finetune-dp4", "t5base-batchgen",
        "t5large-serve"]
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("mutate, what", [
    (lambda d: d["workloads"][0].__setitem__("name", "t5 base"), "space"),
    (lambda d: d["workloads"][0].__setitem__("name", "a/b"), "slash"),
    (lambda d: d["workloads"][0].__setitem__("name", "x" * 65), "too long"),
    (lambda d: d["workloads"][0].__setitem__("traffic", "a,b"), "comma"),
    (lambda d: d["end_to_end"][0].__setitem__("unit", "tokens per second"),
     "unit with spaces"),
    (lambda d: d["end_to_end"][0].__setitem__("unit", "µs"), "Greek"),
    (lambda d: d["end_to_end"][0].__setitem__("bound", 0.2), "bound"),
    (lambda d: d["end_to_end"][0].__setitem__("why", "x"), "extra key"),
    (lambda d: d["per_layer"][0].__setitem__("moves", "nothing"), "moves"),
    (lambda d: d["end_to_end"][0].__setitem__("source", "program_span"),
     "end-to-end source"),
    (lambda d: d.__setitem__("run_seconds", 52), "run_seconds"),
    (lambda d: d["workloads"][0].__setitem__("chips", 4), "four-chip share"),
    (lambda d: d["workloads"][0].__setitem__("why", "a\tb"), "tab"),
    (lambda d: d.__setitem__("extra", 1), "top-level key"),
])
def test_the_loader_refuses(mutate, what):
    doc = copy.deepcopy(_doc())
    mutate(doc)
    with pytest.raises(manifest.ManifestError):
        manifest.validate(doc)


def test_every_metric_has_its_file_and_reader(bench):
    for group, folder in manifest.FOLDERS.items():
        declared = {m["name"] for m in bench.doc[group]}
        files = set()
        for fn in os.listdir(os.path.join(manifest.HERE, folder)):
            with open(os.path.join(manifest.HERE, folder, fn)) as f:
                m = json.load(f)
            assert fn == m["name"] + ".json"
            assert set(m) <= {"name", "reader", "args", "doc"}
            files.add(m["name"])
            bench.module("readers", m["reader"]).read  # the reader exists
        assert files == declared


def test_every_cell_has_its_files(bench):
    for w in bench.doc["workloads"]:
        t = bench.traffic(w)
        bench.module("kinds", t["kind"]).run
        assert "rehearse" in t
        names = {m["name"] for m in bench.metrics("end_to_end", w["name"])}
        assert "setup_s" in names and len(names) >= 2
        assert bench.metrics("per_layer", w["name"])


@pytest.mark.parametrize("name, preset", [
    ("flan-t5-base", "flan_t5_base"), ("flan-t5-large", "flan_t5_large")])
def test_configs_are_the_presets_key_by_key(bench, name, preset):
    from tpu_air.models.t5 import T5Config

    from benchmark import weights

    cfg = bench.config(name)
    want = getattr(T5Config, preset)().to_dict()
    shape_keys = ("vocab_size", "d_model", "d_kv", "d_ff", "num_layers",
                  "num_decoder_layers", "num_heads",
                  "relative_attention_num_buckets",
                  "relative_attention_max_distance", "dropout_rate",
                  "layer_norm_epsilon", "feed_forward_proj",
                  "tie_word_embeddings", "pad_token_id", "eos_token_id",
                  "decoder_start_token_id")
    for key in shape_keys:
        assert cfg[key] == want[key], key
    assert cfg["reduced"] == [] and "assumed" in cfg
    assert cfg["source"].startswith("https://huggingface.co/google/" + name)
    # and what the harness builds from the file is the preset
    built = weights.t5_config(cfg, want["dtype"]).to_dict()
    assert built == want


FIFTH = {"name": "t5base-batchgen-short", "config": "flan-t5-base",
         "traffic": "t5base-batchgen-short", "chips": 1,
         "why": "a fifth cell given as files only"}


def test_a_fifth_cell_added_as_files_is_found_and_rehearsed(tmp_path):
    doc = _doc()
    doc["workloads"].append(FIFTH)
    for m in doc["end_to_end"] + doc["per_layer"]:
        if m.get("workloads") == ["t5base-batchgen"]:
            m["workloads"] = ["t5base-batchgen", FIFTH["name"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    extra = tmp_path / "benchmark"
    (extra / "traffic").mkdir(parents=True)
    (extra / "traffic" / "t5base-batchgen-short.json").write_text(json.dumps({
        "kind": "batchgen", "rows_per_block": 64, "encoder_len": 128,
        "max_new_tokens": 16, "dtype": "bfloat16", "nominal_block_ms": 200,
        "trace_call": 3,
        "rehearse": {"rows_per_block": 2, "encoder_len": 8,
                     "max_new_tokens": 3, "dtype": "float32",
                     "nominal_block_ms": 500}}))
    # a metric of its own too: one manifest entry, one file naming a reader
    doc["per_layer"].append({
        "name": "gen_call_ms_p95", "unit": "ms", "layer": "generate",
        "moves": "gen_seq_s", "source": "host_clock", "better": "lower",
        "workloads": [FIFTH["name"]]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    (extra / "layer_metrics").mkdir()
    (extra / "layer_metrics" / "gen_call_ms_p95.json").write_text(json.dumps({
        "name": "gen_call_ms_p95", "reader": "quantile",
        "args": {"series": "gen_call_ms", "q": 0.95}}))
    b = manifest.Benchmark(str(tmp_path))
    assert b.cell(FIFTH["name"])["traffic"] == "t5base-batchgen-short"
    assert b.traffic(b.cell(FIFTH["name"]))["rows_per_block"] == 64
    got = {m["name"] for m in b.metrics("per_layer", FIFTH["name"])}
    assert {"gen_call_ms_p95", "gen_call_ms_p50", "worker_compile_s"} <= got
    assert "train_mfu" not in got
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", FIFTH["name"], "--rehearse", "--seconds", "1",
         "--trace", "1", "--root", str(tmp_path)],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith("rehearsal of t5base-batchgen-short: ok")
    assert "gen_call_ms_p95" in last
