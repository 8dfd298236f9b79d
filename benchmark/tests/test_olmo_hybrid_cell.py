"""The ``olmo-hybrid-7b-assist`` cell's files at the rehearsal size
(``olmo-hybrid-tiny-assist``, CPU): the tree passes through the unedited
serve driver with the ``open_loop`` generator — bucketed prefill handing the
matrix state on at the last real token, decode over the slot-major state
beside the paged pool — the new readers read the program's counters, and the
cell's files agree with each other, with the builder, with the catalog's row
and with the issue's traffic.

The rehearsal computes in float32 where the cell computes in bf16: at hidden
64 eight delta-rule layers hand a bf16 rounding on, more than doubled each
(tests/test_olmo_hybrid.py), to a logit gap of 0.07 against the harness's
limit of 0.06, which was set on models thousands wide (hidden 128 and 256
read 0.11-0.54; the cell itself reads 0.007 on the chip: PERF.md section 6,
PR 46). What the rehearsal holds is the harness's path."""
import json
import os
import sys

from benchmark.tests.test_rehearsal import ROOT, result, run

BENCH = os.path.join(ROOT, "benchmark")


def read(sub, name):
    with open(os.path.join(BENCH, sub, f"{name}.json")) as f:
        return json.load(f)


def test_the_tree_runs_the_cell_and_the_new_readers_read():
    proc = run("olmo-hybrid-tiny-assist", 1, seconds="2")
    line = result(proc)
    assert line["correct"] is True and line["failed"] == 0
    assert {"gdn_state_gb", "batch_occupancy", "check_logit_gap_max",
            "decode_tick_ms_p50", "queue_p90_ms"} <= set(line["metrics"])
    # 8 slots x 6 mixers x (2 x 16 x 32 f32 + 3 x 128 f32)
    assert line["metrics"]["gdn_state_gb"]["value"] * 1e9 \
        == 8 * 6 * (2 * 16 * 32 * 4 + 3 * 128 * 4)
    assert "check tokens_within_reference_gap: True" in proc.stdout
    # the device-trace readers find no device on the CPU and say nothing
    assert not {"gdn_mixer_ms_per_step", "gdn_rule_ms_per_step",
                "gdn_state_roofline", "gated_delta_rule_roofline"} \
        & set(line["metrics"])


def test_the_cells_files_agree():
    sys.path.insert(0, ROOT)
    from flexflow_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                 olmo_hybrid_param_count)

    config = read("configs", "olmo-hybrid-7b")
    cell = read("workloads", "olmo-hybrid-7b-assist")
    mix = read("traffic", "assist-512")
    cfg = OlmoHybridConfig(batch_size=8, **{
        f: config[k] for f, k in config["builder"]["fields"].items()})
    assert olmo_hybrid_param_count(cfg) == config["parameters_held"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    # every published number stands but the two keys cut, and those are
    # four whole periods of the published eight
    for key, published in config["published"].items():
        if key not in config["reduced"]:
            assert config[key] == published, key
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 16
    assert config["layer_types"] == config["published"]["layer_types"][:16]
    assert config["layer_types"][:4] == ["linear_attention"] * 3 \
        + ["full_attention"]
    assert config["assumed"]["norm_placement"] and config["deployment"]
    # the issue's traffic, as given
    assert mix["generator"] == "open_loop"
    assert mix["prompt_len"] == {"median": 256, "sigma": 0.8, "min": 32,
                                 "max": 1024}
    assert mix["output_len"] == {"median": 512, "sigma": 0.6, "min": 64,
                                 "max": 1536}
    eng = cell["engine"]
    assert eng["max_decode_len"] == mix["max_total_tokens"] == 2560
    assert eng["buckets"] == [128, 256, 512, 1024]
    assert eng["n_slots"] == 64 and (eng["kv_pool_blocks"] - 1) * 16 == 49152
    assert (cell["kind"], cell["chips"]) == ("serve", 1)
    # no chunking, no prefix cache: a recurrent state has neither
    flags = config["compile_flags"] + cell["compile_flags"]
    assert "--prefill-chunk-tokens" not in flags
    assert flags[flags.index("--prefix-cache") + 1] == "off"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]].count(cell["name"]) == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


def test_the_catalogs_numbers_stand(tmp_path):
    """Every number of the catalog row's ``config`` is in the file under the
    same key, but the keys listed as reduced (the row is copied here: the
    catalog lies outside the repository)."""
    catalog = {
        "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "max_position_embeddings": 65536,
        "rms_norm_eps": 1e-06, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4}
    config = read("configs", "olmo-hybrid-7b")
    for key, value in catalog.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["rope_parameters"] == {"rope_theta": None}
    assert config["linear_allow_neg_eigval"] is True
    assert config["tie_word_embeddings"] is False
